# Pod-scale dry runs on CPU hosts: set device count BEFORE jax init.
import os
if os.environ.get("REPRO_FORCE_DEVICES"):
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count="
        + os.environ["REPRO_FORCE_DEVICES"])

"""Serving launcher: batched decode with the CORE monitor attached.

    python -m repro.launch.serve --arch qwen2.5-14b --smoke --tokens 32
        [--guard "SELECT ... PARTITION BY [lane]"]

Production shape: prefill builds lane caches, the decode loop emits one CER
event per (lane, token) into the partitioned engine; matches surface as
guardrail hits alongside the generated tokens.

``--service`` swaps the in-process host executor for the resilient
:class:`repro.runtime.StreamService` runtime (DESIGN.md §12): the decode
loop submits raw dicts, the service validates / chunks / encodes off the
decode thread, and guardrail alerts surface through at-least-once sinks
backed by a durable emission log under ``--service-dir``.
"""
import argparse
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import ALIASES, get_config, get_smoke_config
from ..core import Event, compile_query
from ..models import init_params, make_serve_step, prefill
from ..sharding import DECODE_RULES, set_rules
from .mesh import make_host_mesh, make_production_mesh, use_mesh

DEFAULT_GUARD = """
SELECT * FROM Tokens
WHERE TOK AS a ; TOK AS b ; TOK AS c
FILTER a[logp < -2.5] AND b[logp < -2.5] AND c[logp < -2.5]
WITHIN 8 events
PARTITION BY [lane]
"""


def grow_caches(caches, tgt):
    def pad(v, axis):
        w = [(0, 0)] * v.ndim
        w[axis] = (0, tgt - v.shape[axis])
        return jnp.pad(v, w)

    segs = []
    for seg in caches["segments"]:
        seg2 = {}
        for k, v in seg.items():
            if k == "mixer" and isinstance(v, dict):
                m2 = {}
                for kk, vv in v.items():
                    if kk in ("k", "v"):
                        m2[kk] = pad(vv, vv.ndim - 3)
                    elif kk in ("c_kv", "k_rope"):
                        m2[kk] = pad(vv, vv.ndim - 2)
                    else:
                        m2[kk] = vv
                seg2[k] = m2
            else:
                seg2[k] = v
        segs.append(seg2)
    return dict(caches, segments=segs)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--lanes", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--guard", default=DEFAULT_GUARD)
    ap.add_argument("--service", action="store_true",
                    help="route the guard through the StreamService "
                         "runtime (validation, DLQ, durable alerts) "
                         "instead of the in-process host executor")
    ap.add_argument("--service-dir", default=None, metavar="DIR",
                    help="durable state directory for --service "
                         "(checkpoints, emission log, DLQ); a temp dir "
                         "when omitted")
    args = ap.parse_args()

    arch = ALIASES.get(args.arch, args.arch)
    cfg = get_smoke_config(arch) if args.smoke else get_config(arch)
    mesh = make_host_mesh() if args.smoke else make_production_mesh()

    with set_rules(DECODE_RULES), use_mesh(mesh):
        params, _ = init_params(cfg, jax.random.PRNGKey(0))
        B, S0 = args.lanes, args.prompt_len
        S_max = S0 + args.tokens
        batch = {"tokens": jax.random.randint(
            jax.random.PRNGKey(1), (B, S0), 0, cfg.vocab_size)}
        if cfg.frontend == "vision_stub":
            batch["patches"] = jnp.ones(
                (B, cfg.frontend_seq, cfg.frontend_dim), jnp.float32)
        if cfg.encoder_layers:
            batch["frames"] = jnp.ones((B, cfg.encoder_seq, cfg.d_model),
                                       jnp.float32)
        logits, caches = prefill(params, cfg, batch)
        caches = grow_caches(caches, S_max +
                             (cfg.frontend_seq
                              if cfg.frontend == "vision_stub" else 0))
        serve_step = jax.jit(make_serve_step(cfg))
        q = compile_query(args.guard)

        svc = guard = None
        alerts = []
        if args.service:
            from ..runtime import EventValidator, StreamService
            from ..vector import PartitionedStreamingEngine, VectorEngine
            ve = VectorEngine(q)
            pse = PartitionedStreamingEngine(
                ve, q.query.partition_by, chunk_len=16,
                num_lanes=max(4, args.lanes))
            sdir = args.service_dir or tempfile.mkdtemp(prefix="serve_svc_")
            svc = StreamService(
                pse, sdir,
                validator=EventValidator(allowed_types={"TOK"}),
                sinks=[lambda c, h: alerts.extend(h)])
        else:
            guard = q.make_executor(max_enumerate=1)

        prefix = cfg.frontend_seq if cfg.frontend == "vision_stub" else 0
        tok = jnp.argmax(logits[:, -1, :], axis=-1)[:, None]
        fired = 0
        for t in range(args.tokens):
            logits_t, caches = serve_step(params, tok, caches,
                                          S0 + t + prefix)
            logp = jax.nn.log_softmax(logits_t.astype(jnp.float32), axis=-1)
            tok = jnp.argmax(logits_t, axis=-1)[:, None]
            chosen = np.take_along_axis(np.asarray(logp), np.asarray(tok),
                                        axis=1)[:, 0]
            for lane in range(B):
                attrs = {"lane": lane, "logp": float(chosen[lane]),
                         "tok": int(tok[lane, 0])}
                if svc is not None:
                    svc.submit(dict(attrs, type="TOK"),
                               block=True, timeout=120.0)
                else:
                    fired += len(guard.process(Event("TOK", attrs)))
    if svc is not None:
        svc.drain(pad=True)
        m = svc.metrics
        svc.close()
        print(f"generated {args.tokens} × {B} lanes; "
              f"{len(alerts)} guardrail alerts across {m.chunks} chunks "
              f"(compile_count={svc.engine.compile_count}, durable log "
              f"at {svc.directory})")
    else:
        print(f"generated {args.tokens} × {B} lanes; "
              f"guardrail fired {fired}×")


if __name__ == "__main__":
    main()
