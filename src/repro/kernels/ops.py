"""jit'd public wrappers around the Pallas kernels.

Handles: TPU-alignment padding (S → ×128 MXU lanes, W → ×8 f32 sublanes,
B → ×b_tile), the kernel-or-XLA route (:func:`plan_pipeline`, recorded by
the engines as a :class:`Route`), and re-slicing outputs back to logical
shapes.  Pallas kernels run compiled on a TPU and in interpret mode only on
the CPU backend or when the caller asks for it.  The pure-jnp oracles live in
:mod:`repro.kernels.ref`; tests assert allclose between the two on shape /
dtype sweeps.

Pipeline routing (DESIGN.md §3/§5): :func:`cer_pipeline` is the single entry
point for the device CER pipeline and routes between

* ``impl="fused"``   — ONE dispatch: the fused Pallas kernel
  (:mod:`repro.kernels.fused_scan`), or, when the shapes do not fit it
  (ring not ×8, VMEM estimate over the chip's scoped limit), one fused XLA
  computation (callers jit it as a unit, so the ``bits``/``class_ids``
  intermediates never round-trip through host or dispatch boundaries).
  Which of the two, and why, is the :class:`Route` the engines record.
* ``impl="unfused"`` — the legacy three-dispatch path (bit-vector kernel →
  class gather → CEA scan kernel), kept as a perf baseline and oracle.
* ``impl="ref"``     — pure-jnp oracles end to end.

``start_pos`` is dynamic everywhere: pass a Python int *or* a traced int32
scalar; one compiled executable serves every chunk offset.

Windows (DESIGN.md §9): :func:`cer_pipeline` takes either the legacy
count-window ``epsilon=`` or a :class:`repro.kernels.window.DeviceWindow`
(``window=``) — time windows add a ``(T, B)`` f32 ``event_ts`` operand and
carry the ``{"C", "ts", "ovf"}`` state pytree through the same signatures.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from . import ref
from .bitvector import bitvector_pallas
from .cea_scan import cea_scan_multi_pallas, cea_scan_pallas
from .fused_scan import DEFAULT_T_TILE, fused_scan_pallas
from .window import TS_EMPTY, DeviceWindow

#: Scoped VMEM a Pallas kernel may use without raising the compiler's
#: limit, keyed by ``jax.Device.device_kind`` (JAX Pallas TPU docs: 16 MiB
#: default scoped VMEM on v5e, of 128 MiB physical).  A TPU kind missing
#: here is an error — add its row — never a guessed default.
VMEM_LIMIT_BYTES = {"TPU v5 lite": 16 * 1024 * 1024}

#: The chip whose limits the CPU interpreter (and a compile for a described
#: chip) routes by, so interpret-mode runs take the kernel/XLA decisions a
#: deployment on that chip would.
TARGET_KIND = "TPU v5 lite"

IMPLS = ("fused", "unfused", "ref")


@dataclass(frozen=True)
class Route:
    """The path one compiled stage takes, and why.

    ``path`` is ``"pallas"`` (the fused kernel), ``"unfused"`` (the legacy
    three-dispatch kernels) or ``"xla"``.  Engines decide it from shapes at
    construction and keep it per stage (``engine.routes``), so no switch
    from kernel to XLA or to interpret mode goes unrecorded.
    """

    path: str
    reason: str
    interpret: bool = False
    b_tile: int = 8
    t_tile: int = 1

    def describe(self) -> str:
        mode = " (interpret mode)" if self.interpret else ""
        return f"{self.path}{mode}: {self.reason}"


#: the pure-jnp oracle's route (``impl="ref"``)
REF_ROUTE = Route("xla", "impl='ref': the caller asked for the pure-jnp "
                         "oracle")

def arena_route(tables, arena_impl: str = "block") -> Route:
    """The arena stage's route for ``tables`` (``tecs_arena.ArenaTables``).

    The builder has no kernel: it is one XLA computation on every
    platform.  The block builder's fold reads each target's predecessor
    cells through static slices and selects over its candidate sources
    (:func:`repro.kernels.ref.fold_sources`); the reason names the largest
    fan-in, so a run's set-up log states which fold it compiled.
    """
    if arena_impl == "fold":
        return Route("xla", "arena_impl='fold': the per-event fold oracle, "
                            "one XLA scan with store scatters")
    cand = ref.fold_sources(np.asarray(tables.pred_idx))
    fan_in = max(len(c) for per_k in cand for c in per_k)
    return Route("xla", "the tECS block builder is one XLA computation on "
                        "every platform (no Pallas kernel); its fold reads "
                        "predecessor cells by static slices and selects, "
                        f"largest fan-in {fan_in}, no state-axis gather")


def vmem_limit(device_kind: str) -> int:
    """Scoped VMEM budget of a TPU kind (:data:`VMEM_LIMIT_BYTES`)."""
    try:
        return VMEM_LIMIT_BYTES[device_kind]
    except KeyError:
        raise ValueError(
            f"no VMEM budget is known for TPU kind {device_kind!r}; add it "
            "to repro.kernels.ops.VMEM_LIMIT_BYTES") from None


def _default_interpret(interpret: Optional[bool]) -> bool:
    """Interpret mode on the CPU backend only, unless the caller says."""
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise ValueError(f"Pallas TPU kernels cannot run on {backend!r}; "
                         "pass use_pallas=False")
    return backend == "cpu"


def _target_kind() -> str:
    """Device kind whose limits apply: the attached TPU's, else the
    target chip's (interpret mode, or a compile for a described chip)."""
    dev = jax.devices()[0]
    return dev.device_kind if dev.platform == "tpu" else TARGET_KIND


def _pad_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def ring_size(epsilon: int) -> int:
    """Ring-buffer slots for window ε, aligned to the f32 sublane width."""
    return _pad_to(epsilon + 1, 8)


def _start_arr(start_pos: Union[int, jnp.ndarray]) -> jnp.ndarray:
    """Dynamic start position → (1,) int32 SMEM operand (never a static)."""
    return jnp.reshape(jnp.asarray(start_pos, jnp.int32), (1,))


def _lane_arr(x, B: int, pad_to: int, fill: int) -> jnp.ndarray:
    """Scalar-or-(B,) operand → (pad_to, 1) int32 lane column for the fused
    kernel; padded lanes get ``fill``."""
    a = jnp.asarray(x, jnp.int32)
    if a.ndim == 0:
        a = jnp.broadcast_to(a, (B,))
    a = jnp.pad(a, (0, pad_to - B), constant_values=fill)
    return a.reshape(pad_to, 1)


def class_indicator(class_of: np.ndarray, num_classes: int) -> jnp.ndarray:
    """``(2^k,)`` class lookup → ``(2^k, C)`` one-hot indicator.

    The fused kernel folds bit-vectors into classes with an MXU matmul
    against this table instead of a dynamic gather.  Rows are padded to the
    f32 sublane width with all-zero rows (never selected: bits < 2^k);
    column padding to the aligned class count happens in cer_pipeline.
    """
    class_of = np.asarray(class_of)
    V = class_of.shape[0]
    ind = np.zeros((_pad_to(max(V, 1), 8), num_classes), dtype=np.float32)
    ind[np.arange(V), class_of] = 1.0
    return jnp.asarray(ind)


# ---------------------------------------------------------------------------
# bit-vector
# ---------------------------------------------------------------------------


def bitvector(attrs: jnp.ndarray, specs: Sequence[Tuple[int, int, float]],
              *, use_pallas: bool = True, interpret: Optional[bool] = None
              ) -> jnp.ndarray:
    """(B, A) f32 → (B,) int32 packed predicate bits."""
    if not use_pallas:
        idx = jnp.asarray([s[0] for s in specs], dtype=jnp.int32)
        ops = jnp.asarray([s[1] for s in specs], dtype=jnp.int32)
        thr = jnp.asarray([s[2] for s in specs], dtype=jnp.float32)
        return ref.bitvector_ref(attrs, idx, ops, thr)
    interpret = _default_interpret(interpret)
    B, A = attrs.shape
    b_tile = min(256, _pad_to(B, 8))
    Bp = _pad_to(B, b_tile)
    if Bp != B:
        attrs = jnp.pad(attrs, ((0, Bp - B), (0, 0)))
    out = bitvector_pallas(attrs, specs, b_tile=b_tile, interpret=interpret)
    return out[:B]


# ---------------------------------------------------------------------------
# CEA scan
# ---------------------------------------------------------------------------


def cea_scan(class_ids: jnp.ndarray, m_all: jnp.ndarray, finals: jnp.ndarray,
             c0: jnp.ndarray, *, epsilon: int,
             start_pos: Union[int, jnp.ndarray] = 0,
             init_state: int = 1, use_pallas: bool = True,
             interpret: Optional[bool] = None, b_tile: int = 8
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Windowed CEA scan over T events for B streams.

    class_ids (T, B) int32 | m_all (C, S, S) f32 | finals (S,) | c0 (B, W, S)
    with W ≥ epsilon+1 → (matches (T, B) f32, c_final (B, W, S) f32).

    ``start_pos`` may be a Python int or a traced int32 scalar — it reaches
    the kernel as a dynamic SMEM operand, so chunked callers reuse one
    compiled executable across chunks (DESIGN.md §5).

    Ring arithmetic is exact under padding: the kernel evicts start j-ε-1
    and seeds start j each step, so any ring size W ≥ ε+1 gives identical
    semantics (the padded slots simply stay empty).
    """
    T, B = class_ids.shape
    NC, S, _ = m_all.shape
    W = c0.shape[1]
    if not use_pallas:
        return _scan_xla(class_ids, m_all, finals, c0, epsilon, start_pos,
                         init_state)

    interpret = _default_interpret(interpret)
    if W % 8 != 0:
        # Ring arithmetic is mod W, so W cannot be padded here without
        # stranding carried-over starts: the caller must allocate the ring at
        # ring_size(epsilon) (×8), or ask for the XLA path.
        raise ValueError(f"the scan kernel needs a ring of ×8 slots, got "
                         f"W={W}; allocate ring_size(epsilon) or pass "
                         "use_pallas=False")
    # --- TPU alignment padding ---------------------------------------------
    Sp = _pad_to(S, 128)
    Bp = _pad_to(B, b_tile)
    NCp = _pad_to(NC, 8)
    m_pad = jnp.pad(m_all, ((0, NCp - NC), (0, Sp - S), (0, Sp - S)))
    f_pad = jnp.pad(finals.astype(jnp.float32), (0, Sp - S))[None, :]
    c_pad = jnp.pad(c0, ((0, Bp - B), (0, 0), (0, Sp - S)))
    ids_pad = jnp.pad(class_ids.T, ((0, Bp - B), (0, 0)))  # (Bp, T)

    vmem = 4 * (b_tile * W * Sp * 2 + NCp * Sp * Sp + b_tile * W * Sp)
    if vmem > vmem_limit(_target_kind()):
        raise ValueError(f"cea_scan VMEM budget exceeded: {vmem} bytes "
                         f"(W={W}, S={Sp}, C={NCp}, b_tile={b_tile})")

    matches, c_fin = cea_scan_pallas(
        ids_pad, m_pad, f_pad, c_pad, _start_arr(start_pos),
        epsilon=epsilon, init_state=init_state,
        b_tile=b_tile, interpret=interpret)
    return matches[:B].T, c_fin[:B, :W, :S]


def _scan_xla(class_ids, m_all, finals, c0, epsilon, start_pos, init_state):
    c_fin, matches = ref.cea_scan_ref(c0, m_all, class_ids, finals,
                                      epsilon=epsilon, start_pos=start_pos,
                                      init_state=init_state)
    return matches, c_fin


def cea_scan_multi(class_ids: jnp.ndarray, m_all: jnp.ndarray,
                   finals_q: jnp.ndarray, c0: jnp.ndarray,
                   *, init_mask: jnp.ndarray, epsilon: int,
                   start_pos: Union[int, jnp.ndarray] = 0,
                   use_pallas: bool = True,
                   interpret: Optional[bool] = None, b_tile: int = 8
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Packed multi-query scan (vector/multiquery.py).

    class_ids (T, B) | m_all (C, S, S) | finals_q (Q, S) | c0 (B, W, S)
    → (matches (T, B, Q), c_final).
    """
    T, B = class_ids.shape
    NC, S, _ = m_all.shape
    NQ = finals_q.shape[0]
    W = c0.shape[1]
    if not use_pallas:
        c_fin, m = ref.cea_scan_multi_ref(c0, m_all, class_ids, finals_q,
                                          init_mask, epsilon,
                                          start_pos=start_pos)
        return m, c_fin
    if W % 8 != 0:
        raise ValueError(f"the scan kernel needs a ring of ×8 slots, got "
                         f"W={W}; pass use_pallas=False for the XLA path")
    interpret = _default_interpret(interpret)
    Sp = _pad_to(S, 128)
    Bp = _pad_to(B, b_tile)
    NCp = _pad_to(NC, 8)
    NQp = _pad_to(NQ, 8)
    m_pad = jnp.pad(m_all, ((0, NCp - NC), (0, Sp - S), (0, Sp - S)))
    f_pad = jnp.pad(finals_q.astype(jnp.float32),
                    ((0, NQp - NQ), (0, Sp - S)))
    i_pad = jnp.pad(init_mask.astype(jnp.float32), (0, Sp - S))[None, :]
    c_pad = jnp.pad(c0, ((0, Bp - B), (0, 0), (0, Sp - S)))
    ids_pad = jnp.pad(class_ids.T, ((0, Bp - B), (0, 0)))
    matches, c_fin = cea_scan_multi_pallas(
        ids_pad, m_pad, f_pad, i_pad, c_pad, _start_arr(start_pos),
        epsilon=epsilon, b_tile=b_tile, interpret=interpret)
    return jnp.moveaxis(matches[:B, :, :NQ], 0, 1), c_fin[:B, :, :S]


# ---------------------------------------------------------------------------
# fused single-pass pipeline
# ---------------------------------------------------------------------------


def _tile_bytes(*shape: int) -> int:
    """VMEM bytes of a 4-byte array, last two dims padded to the (8, 128)
    tile."""
    lead = int(np.prod(shape[:-2], dtype=np.int64)) if len(shape) > 2 else 1
    sub = shape[-2] if len(shape) > 1 else 1
    return 4 * lead * _pad_to(sub, 8) * _pad_to(shape[-1], 128)


def fused_vmem_bytes(*, A: int, W: int, S: int, NC: int, NQ: int, V: int,
                     b_tile: int, t_tile: int, timed: bool, latest: bool,
                     consume: bool, trace: bool) -> int:
    """Estimated VMEM of one fused-kernel grid step (padded shapes).

    Pipelined blocks count twice (double buffering); the ``(b_tile, W, S)``
    ring counts for its in/out blocks, its scratch and the step's live
    temporaries.
    """
    Sp, NCp, NQp = _pad_to(S, 128), _pad_to(NC, 8), _pad_to(NQ, 8)
    ring = _tile_bytes(b_tile, W, Sp)
    step = _tile_bytes(b_tile, t_tile)                    # one (b, t) row
    blocks = (2 * _tile_bytes(b_tile, 1)                  # start, valid
              + A * step                                  # attrs
              + _tile_bytes(V, NCp) + _tile_bytes(NCp, Sp * Sp)
              + _tile_bytes(NQp, Sp) + _tile_bytes(1, Sp)
              + NQp * step                                # matches
              + (step if trace else 0)
              + (step                                     # event ts
                 + 2 * _tile_bytes(b_tile, W)             # ts ring in/out
                 + 2 * _tile_bytes(b_tile, 1) if timed else 0)
              + (_tile_bytes(1, NQp) if latest else 0)
              + (_tile_bytes(NQp, Sp) if consume else 0))
    temps = ((A + 1) * step                               # loaded blocks
             + _tile_bytes(b_tile, V) + _tile_bytes(b_tile, Sp * Sp)
             + _tile_bytes(b_tile, Sp, Sp)                # gathered M
             + 3 * ring                                   # C, C_new, mix
             + _tile_bytes(b_tile * W, NQp)               # per-slot counts
             + (_tile_bytes(b_tile, W) + _tile_bytes(b_tile, 1)
                if timed else 0)
             + (2 * _tile_bytes(b_tile, W, W)
                + 2 * _tile_bytes(b_tile, W, NQp) if latest else 0))
    return 2 * blocks + 3 * ring + temps


def plan_pipeline(*, T: int, B: int, A: int, W: int, S: int, NC: int,
                  NQ: int, V: int, timed: bool = False, per_lane: bool = False,
                  latest: bool = False, consume: bool = False,
                  trace: bool = False, impl: str = "fused",
                  use_pallas: bool = True, interpret: Optional[bool] = None,
                  b_tile: int = 8, t_tile: Optional[int] = None) -> Route:
    """The :class:`Route` :func:`cer_pipeline` takes for these shapes.

    Pure in its arguments and the attached device, so an engine can decide
    it once at construction and record it.  ``T`` is the chunk (steps per
    call), ``B`` the lanes, ``W`` the ring, ``S``/``NC``/``NQ`` the states,
    classes and queries, ``V`` the indicator rows.
    """
    if impl not in IMPLS:
        raise ValueError(f"impl must be one of {IMPLS}, got {impl!r}")
    if t_tile is not None and T % t_tile != 0:
        # a value invalid for the kernel must fail on every route
        raise ValueError(f"t_tile must divide the chunk length: {t_tile} "
                         f"vs T={T}")
    if impl == "ref":
        return REF_ROUTE
    if not use_pallas:
        return Route("xla", "use_pallas=False: the caller asked for XLA")
    if impl == "unfused":
        if per_lane or timed or latest or consume:
            return Route("xla", "the unfused kernels take one scalar offset "
                                "and count windows under ANY only")
        return Route("unfused", "impl='unfused': the three-dispatch baseline",
                     interpret=_default_interpret(interpret), b_tile=b_tile)
    interpret = _default_interpret(interpret)
    kind = _target_kind()
    limit = vmem_limit(kind)
    if t_tile is None:
        # one block of the whole chunk, or lane-width blocks over a chunk
        # padded with dead steps
        t_tile = min(T, DEFAULT_T_TILE)
    elif not interpret and t_tile != T and t_tile % 128 != 0:
        raise ValueError(f"t_tile={t_tile} must be a multiple of 128 (or "
                         f"all T={T} events) for the compiled kernel")
    if W % 8 != 0:
        return Route("xla", f"the ring has W={W} slots, not a multiple of "
                            "the 8 f32 sublanes")
    if not interpret and b_tile % 8 != 0 and _pad_to(B, b_tile) != b_tile:
        raise ValueError(f"b_tile={b_tile} must be a multiple of 8 (or "
                         f"cover all {B} lanes) for the compiled kernel")
    vmem = fused_vmem_bytes(A=A, W=W, S=S, NC=NC, NQ=NQ, V=V, b_tile=b_tile,
                            t_tile=t_tile, timed=timed, latest=latest,
                            consume=consume, trace=trace)
    if vmem > limit:
        return Route("xla", f"VMEM estimate {vmem} B for a ({b_tile}, {W}, "
                            f"{_pad_to(S, 128)}) ring tile exceeds the "
                            f"{limit} B scoped limit of {kind}")
    return Route("pallas", f"the ({b_tile}, {W}, {_pad_to(S, 128)}) ring "
                           f"tile fits VMEM ({vmem} of {limit} B on {kind})",
                 interpret=interpret, b_tile=b_tile, t_tile=t_tile)


def cer_pipeline(attrs: jnp.ndarray,
                 specs: Sequence[Tuple[int, int, float]],
                 class_of: jnp.ndarray, class_ind: jnp.ndarray,
                 m_all: jnp.ndarray, finals_q: jnp.ndarray,
                 c0, *, init_mask: jnp.ndarray,
                 epsilon: Optional[int] = None,
                 window: Optional[DeviceWindow] = None,
                 event_ts: Optional[jnp.ndarray] = None,
                 start_pos: Union[int, jnp.ndarray] = 0,
                 valid_counts: Optional[jnp.ndarray] = None,
                 route: Route,
                 return_trace: bool = False,
                 latest_q: Optional[jnp.ndarray] = None,
                 consume_sq: Optional[jnp.ndarray] = None
                 ) -> Tuple[jnp.ndarray, ...]:
    """Full device CER pipeline: raw attributes → per-position match counts.

    attrs (T, B, A) f32 | class_of (2^k,) int32 | class_ind (≥2^k, C) f32
    | m_all (C, S, S) | finals_q (Q, S) | init_mask (S,) | c0 (B, W, S)
    → (matches (T, B, Q) f32, c_final (B, W, S) f32).

    ``return_trace=True`` appends the per-event symbol-class trace
    ``(T, B) int32`` — the tECS-arena operand (DESIGN.md §7): the arena
    update consumes it instead of re-evaluating predicates on raw events.
    The fused Pallas kernel emits it as a third kernel output; the XLA and
    unfused paths already materialize it.

    Routing: ``route`` is the caller's :class:`Route`, planned by
    :func:`plan_pipeline` from these shapes (engines plan it once at
    construction and record it).  The fused Pallas path needs W ≡ 0
    (mod 8) and the chip's scoped VMEM to hold the indicator, tables and
    state tile; otherwise the route is the fused XLA computation (still one
    dispatch under the caller's jit).  The route's ``t_tile`` is the events
    per fused-kernel grid step; longer chunks are padded with dead steps to
    a multiple of it.

    PARTITION BY lanes (DESIGN.md §6): ``start_pos`` may also be a ``(B,)``
    vector of per-lane substream offsets, and ``valid_counts`` a ``(B,)``
    int32 vector marking each lane's dense prefix of real events this chunk
    (steps past it are exact no-ops for that lane).  The fused Pallas kernel
    and the fused-XLA/ref path support both; the legacy unfused kernels are
    scalar-only, so :func:`plan_pipeline` plans per-lane calls on that impl
    onto the XLA path.

    Selection/consumption (DESIGN.md D2): ``latest_q`` ``(Q,)`` f32 marks
    LAST queries (their counts reduce to the latest live seed slot);
    ``consume_sq`` ``(Q, S)`` f32 maps each CONSUME BY ANY query to the
    packed states it clears after an emitting position.  Both default to
    ``None`` — the classic ANY graph, bit-identical to before.  The legacy
    unfused kernels are count-only ANY; :func:`plan_pipeline` plans either
    operand on that impl onto the fused-XLA path (like ``timed``/``per_lane``).

    Windows (DESIGN.md §9): pass either the legacy ``epsilon=`` (count
    window) or a :class:`repro.kernels.window.DeviceWindow` as ``window=``.
    Time windows additionally take ``event_ts`` ``(T, B) f32`` per-event
    timestamps, and ``c0`` is the ``{"C", "ts", "ovf"}`` state pytree
    (:func:`repro.kernels.window.init_state`) — the returned state has the
    same form.  Time windows route to the fused Pallas kernel or the
    fused-XLA computation (the legacy unfused kernels are count-only).
    """
    with jax.named_scope("scan"):
        if window is None:
            if epsilon is None:
                raise ValueError("cer_pipeline needs epsilon= or window=")
            window = DeviceWindow.events(epsilon)
        timed = window.is_time
        epsilon = window.epsilon
        if timed and event_ts is None:
            raise ValueError("time windows need the event_ts (T, B) operand")
        T, B, A = attrs.shape
        if timed:
            event_ts = jnp.asarray(event_ts, jnp.float32)
            if event_ts.shape != (T, B):
                # (T, B) like attrs — a transposed operand would fail deep in
                # the kernel, or silently mis-evict when T == B
                raise ValueError(f"event_ts must be (T, B) = ({T}, {B}) like "
                                 f"attrs, got {event_ts.shape}")
        NC, S, _ = m_all.shape
        c_ring = c0["C"] if timed else c0
        NQ = finals_q.shape[0]

        if route.path == "xla":
            return _pipeline_xla(attrs, specs, class_of, m_all, finals_q, c0,
                                 init_mask, epsilon, start_pos, valid_counts,
                                 return_trace, window=window,
                                 event_ts=event_ts,
                                 latest_q=latest_q, consume_sq=consume_sq)

        if route.path == "unfused":
            # legacy 3-dispatch path: bits kernel → gather → scan kernel
            bits = bitvector(attrs.reshape(T * B, A), specs,
                             interpret=route.interpret)
            class_ids = class_of[bits].reshape(T, B)
            matches, c_fin = cea_scan_multi(
                class_ids, m_all, finals_q, c0, init_mask=init_mask,
                epsilon=epsilon, start_pos=start_pos,
                interpret=route.interpret, b_tile=route.b_tile)
            if return_trace:
                return matches, c_fin, class_ids.astype(jnp.int32)
            return matches, c_fin

        # --- the fused Pallas kernel -----------------------------------------
        b_tile, t_tile = route.b_tile, route.t_tile
        Sp = _pad_to(S, 128)
        NCp = _pad_to(NC, 8)
        NQp = _pad_to(NQ, 8)
        Bp = _pad_to(B, b_tile)
        Tp = _pad_to(T, t_tile)                      # padded steps are dead
        a_pad = jnp.pad(jnp.transpose(attrs, (2, 1, 0)),
                        ((0, 0), (0, Bp - B), (0, Tp - T)))    # (A, Bp, Tp)
        ind_pad = jnp.pad(class_ind, ((0, 0), (0, NCp - NC)))
        m_flat = jnp.pad(m_all, ((0, NCp - NC), (0, Sp - S), (0, Sp - S))
                         ).reshape(NCp, Sp * Sp)
        f_pad = jnp.pad(finals_q.astype(jnp.float32),
                        ((0, NQp - NQ), (0, Sp - S)))
        i_pad = jnp.pad(init_mask.astype(jnp.float32), (0, Sp - S))[None, :]
        c_pad = jnp.pad(c_ring, ((0, Bp - B), (0, 0), (0, Sp - S)))
        start_lanes = _lane_arr(start_pos, B, Bp, fill=0)
        valid_lanes = _lane_arr(T if valid_counts is None else valid_counts,
                                B, Bp, fill=0)       # padded lanes are dead
        time_kw = {}
        if timed:
            time_kw = dict(
                time_size=float(window.size),
                event_ts=jnp.pad(event_ts.T, ((0, Bp - B), (0, Tp - T))),
                ts_ring0=jnp.pad(c0["ts"], ((0, Bp - B), (0, 0)),
                                 constant_values=TS_EMPTY),
                ovf0=jnp.pad(c0["ovf"].astype(jnp.int32)[:, None],
                             ((0, Bp - B), (0, 0))))
        sem_kw = {}
        if latest_q is not None:
            sem_kw["latest_q"] = jnp.pad(
                jnp.asarray(latest_q, jnp.float32), (0, NQp - NQ))[None, :]
        if consume_sq is not None:
            sem_kw["consume_sq"] = jnp.pad(
                jnp.asarray(consume_sq, jnp.float32),
                ((0, NQp - NQ), (0, Sp - S)))

        res = fused_scan_pallas(
            a_pad, ind_pad, m_flat, f_pad, i_pad, c_pad, start_lanes,
            valid_lanes, specs=tuple(specs), epsilon=epsilon, b_tile=b_tile,
            t_tile=t_tile, interpret=route.interpret, emit_trace=return_trace,
            **time_kw, **sem_kw)
        matches, c_fin = res[0], res[1]
        c_out = c_fin[:B, :, :S]
        if timed:
            c_out = {"C": c_out, "ts": res[2][:B],
                     "ovf": res[3][:B, 0].astype(bool)}
        out = jnp.transpose(matches[:NQ, :B, :T], (2, 1, 0)), c_out
        if return_trace:
            return out + (res[-1][:B, :T].T,)
        return out


def _pipeline_xla(attrs, specs, class_of, m_all, finals_q, c0, init_mask,
                  epsilon, start_pos, valid_counts=None, return_trace=False,
                  window=None, event_ts=None, latest_q=None, consume_sq=None):
    """Fused pipeline as one XLA computation (also the ``ref`` oracle).

    Same dataflow as the fused kernel: under a single jit the ``bits`` /
    ``class_ids`` intermediates live only inside the compiled computation —
    no extra dispatches, no host round trips between stages.
    """
    idx = jnp.asarray([s[0] for s in specs], dtype=jnp.int32)
    ops_ = jnp.asarray([s[1] for s in specs], dtype=jnp.int32)
    thr = jnp.asarray([s[2] for s in specs], dtype=jnp.float32)
    class_ids = ref.class_trace_ref(attrs, idx, ops_, thr, class_of)
    c_fin, matches = ref.cea_scan_multi_ref(c0, m_all, class_ids, finals_q,
                                            init_mask, epsilon,
                                            start_pos=start_pos,
                                            valid_counts=valid_counts,
                                            window=window,
                                            event_ts=event_ts,
                                            latest_q=latest_q,
                                            consume_sq=consume_sq)
    if return_trace:
        return matches, c_fin, class_ids
    return matches, c_fin
