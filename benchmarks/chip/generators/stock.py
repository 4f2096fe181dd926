"""The stock stream of the paper's §6 (``repro.data.streams.stock_stream``'s
shape): a uniform name, BUY or SELL, a volume and a price to the cent, each
uniform and independent; ``stock_time`` advances ``1000 / events_per_sec``
ms an event."""
import numpy as np


def types(gen):
    return list(gen["types"])


def draw(s, n):
    g, rng = s.gen, s.rng
    dt = 1000.0 / float(g["events_per_sec"])
    t0 = s.carry.get("stock_time", 0.0)
    clock = np.cumsum(np.concatenate([[t0], np.full(n, dt)]))[1:]
    s.carry["stock_time"] = float(clock[-1])
    names = np.asarray(g["names"])
    vols = np.asarray(g["volumes"], np.float64)
    lo, hi = g["price"]
    return {
        "type": rng.integers(0, len(g["types"]), n),
        "name": names[rng.integers(0, len(names), n)],
        "volume": vols[rng.integers(0, len(vols), n)],
        "price": np.round(rng.uniform(lo, hi, n), 2),
        "stock_time": clock,
    }
