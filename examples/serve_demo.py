"""Serving demo: the Homa-SRPT scheduler (repro.serving) driving real
batched decode of a Mamba2 model (SSM state caches are position-free, so
ragged continuous batching needs no padding tricks).

    PYTHONPATH=src python examples/serve_demo.py
"""
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.reduced import reduced_config
from repro.models import model as M
from repro.models.params import init_params
from repro.serving.scheduler import HomaScheduler, SchedulerConfig, Request


def main():
    cfg = reduced_config("mamba2-130m")
    params = init_params(M.model_defs(cfg), jax.random.key(0))
    C = 4                                     # decode slots
    sched = HomaScheduler(SchedulerConfig(batch_size=C, overcommit=3,
                                          unsched_limit=4))

    # per-slot SSM caches (batch dim = C)
    shapes = M.cache_shapes(cfg, C, 1)
    caches = jax.tree.map(lambda s: jnp.zeros(s, jnp.bfloat16), shapes,
                          is_leaf=lambda x: isinstance(x, tuple))
    tokens = jnp.zeros((C, 1), jnp.int32)

    decode = jax.jit(lambda p, c, t: M.forward_decode(cfg, p, t, 1, c))

    rng = np.random.default_rng(0)
    for i in range(24):
        sched.submit(Request(rid=i, prompt_len=4,
                             max_new_tokens=int(rng.integers(2, 24)),
                             arrival=0.0))

    slot_of: dict[int, int] = {}
    state = {"caches": caches, "tokens": tokens}

    def decode_fn(batch):
        # place requests into slots (Homa "active" -> decode slot binding)
        free = [s for s in range(C)
                if s not in slot_of.values()]
        for r in batch:
            if r.rid not in slot_of:
                slot_of[r.rid] = free.pop(0)
        logits, deltas = decode(params, state["caches"], state["tokens"])
        # merge SSM cache deltas back per served slot
        def merge(old, new):
            return new.astype(old.dtype)
        state["caches"] = jax.tree.map(merge, state["caches"], deltas)
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        state["tokens"] = nxt[:, None]
        done = []
        for r in batch:
            d = r.remaining <= 1
            if d:
                slot_of.pop(r.rid, None)
            done.append(d)
        return done

    t, steps = 0.0, 0
    while (sched.active or sched.queue) and steps < 2000:
        sched.step(decode_fn, t)
        t += 1.0
        steps += 1

    sl = sched.slowdowns()
    print(f"served {len(sched.finished)}/24 requests in {steps} steps")
    print(f"slowdown: mean {sl.mean():.2f}  p99 {np.percentile(sl, 99):.2f}")
    assert len(sched.finished) == 24
    print("serve_demo OK")


if __name__ == "__main__":
    from repro.jax_cache import enable_compile_cache
    enable_compile_cache()
    main()
