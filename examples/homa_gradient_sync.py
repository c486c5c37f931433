"""Homa-scheduled data-parallel training on 8 (virtual) devices: chunked,
SRPT-ordered, overcommitment-bounded gradient collectives, with optional
int8 compression + error feedback.

    PYTHONPATH=src python examples/homa_gradient_sync.py [--compress]
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax
import jax.numpy as jnp

from repro.configs.reduced import reduced_config
from repro.models import model as M
from repro.models.params import init_params
from repro.training.optimizer import OptConfig, init_opt_state, adamw_update
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.distrib import homa_collectives as HC


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--compress", action="store_true")
    ap.add_argument("--steps", type=int, default=30)
    a = ap.parse_args()

    mesh = jax.make_mesh((8,), ("data",))
    cfg = reduced_config("llama3.2-3b")
    oc = OptConfig(lr=1e-3, warmup_steps=5, total_steps=a.steps,
                   weight_decay=0.01)
    params = init_params(M.model_defs(cfg), jax.random.key(0))
    opt_state = init_opt_state(params, oc)

    scfg = HC.SyncConfig(chunk_bytes=1 << 14, overcommit=7,
                         compress="int8" if a.compress else None)
    err = HC.init_err_state(params, scfg)

    step = HC.build_dp_train_step(
        lambda p, b: M.loss_fn(cfg, p, b)[0],
        lambda p, g, s: adamw_update(p, g, s, oc),
        mesh, scfg)

    dc = DataConfig(seq_len=64, global_batch=16, vocab_size=cfg.vocab_size)
    src = SyntheticLM(dc)
    first = last = None
    for i in range(a.steps):
        batch = {k: jnp.asarray(v) for k, v in src.batch(i).items()}
        params, opt_state, metrics, err = step(params, opt_state, batch, err)
        loss = float(metrics["loss"])
        first = first if first is not None else loss
        last = loss
        if i % 5 == 0:
            print(f"step {i} loss {loss:.4f}")
    assert last < first, (first, last)
    print(f"homa_gradient_sync OK ({'int8' if a.compress else 'f32'}): "
          f"loss {first:.3f} -> {last:.3f} on {jax.device_count()} devices")


if __name__ == "__main__":
    from repro.jax_cache import enable_compile_cache
    enable_compile_cache()
    main()
