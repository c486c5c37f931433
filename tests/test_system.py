"""End-to-end behaviour tests for the paper's system: the full Homa stack
(workload -> priority allocation -> simulation -> SRPT outcomes) plus the
training stack smoke (config -> data -> step -> checkpoint)."""
import numpy as np

from repro.core.sim import SimConfig, simulate
from repro.core.workloads import make_messages


def test_end_to_end_homa_pipeline():
    """Full pipeline: synthesize W2, allocate priorities from its CDF,
    simulate at 70% load, and verify the paper's qualitative outcome —
    small messages see near-ideal latency while the system stays lossless
    and conserves bytes."""
    tbl = make_messages("W2", n_hosts=6, load=0.7, n_messages=800,
                        slot_bytes=256, seed=11)
    cfg = SimConfig(n_hosts=6, protocol="homa", max_slots=40_000,
                    ring_cap=2048)
    res = simulate(cfg, tbl, return_state=True)
    # allocation reflects the workload's byte-weighted CDF (our W2
    # synthesis is heavier-tailed than the paper's — see EXPERIMENTS notes —
    # so it earns fewer unscheduled levels than the paper's ~6)
    assert 1 <= res.alloc.n_unsched <= 7
    # lossless
    assert res.lost_chunks == 0
    # conservation
    s = res.state
    assert int(s["recv"].sum()) + int(s["r_valid"].sum()) \
        == int(s["sent"].sum())
    # small-message tail near ideal
    ok = res.done & (res.size_bytes < 1000)
    assert ok.sum() > 50
    p99 = np.percentile(res.slowdown[ok], 99)
    assert p99 < 3.5, p99
    med = np.median(res.slowdown[res.done])
    assert med < 1.5, med


def test_compile_cache_dir_rule(monkeypatch):
    """Entry scripts' compile cache: ``$JAX_COMPILATION_CACHE_DIR`` stands
    untouched when set; otherwise one fixed directory in the checkout."""
    import jax
    from repro import jax_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert jax_cache.enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = jax_cache.enable_compile_cache()
        assert path == str(jax_cache.CHECKOUT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == path
        assert jax_cache.CHECKOUT_CACHE_DIR.name == ".jax_cache"
        assert jax_cache.CHECKOUT_CACHE_DIR.parent.joinpath(
            "chip_smoke.py").exists()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
