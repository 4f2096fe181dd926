"""Operations and HBM bytes of one ``cer_fused_scan`` call (kernels/fused_scan.py).

Counted from shapes, for the lanes that carry events (``B``, not the
kernel's padding to its lane tile) and for the counting work alone: per
event and lane, the windowed semiring step ``(W, S) x (S, S)`` and the
reduction to the ``NQ`` final vectors; the predicate fold and the one-hot
matrix gathers are the kernel's way of doing a table lookup and are not
counted.  Bytes are what must cross HBM once per call: the attribute block
in, the counts out, the ring in and out, the tables, and in time windows
the timestamps and their ring.
"""
F32 = 4


def cost(shapes):
    T, B, A, W, S = (shapes[k] for k in ("T", "B", "A", "W", "S"))
    NC, V, NQ = shapes["NC"], shapes["V"], shapes["NQ"]
    flops = T * B * (2 * W * S * S + 2 * W * S * NQ)
    nbytes = F32 * (A * B * T + NQ * B * T + 2 * B * W * S
                    + V * NC + NC * S * S + NQ * S + S)
    if shapes.get("timed"):
        nbytes += F32 * (B * T + 2 * B * W + 2 * B)
    if shapes.get("trace"):
        nbytes += F32 * B * T
    return {"flops": float(flops), "bytes": float(nbytes)}
