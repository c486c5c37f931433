"""Homa-scheduled gradient sync vs fused/naive sync — two complementary
views (DESIGN.md §2.2 adaptation):

1. **Structural** (HLO): build the DP train step with homa vs naive sync on
   8 host devices; count collectives and their sizes from the compiled HLO —
   message-orientation means many small collectives instead of a few huge
   ones, and the K-lane barrier chains bound concurrent in-flight bytes.

2. **Predicted wall-time** (simulator): feed the actual gradient chunk trace
   of a model into the packet-level simulator as a Homa message workload on
   the pod interconnect, with a straggler sender injected; compare sync
   completion time homa vs basic. This reuses the paper's own machinery to
   predict the benefit of its scheduling on collective traffic.
"""
from __future__ import annotations

import numpy as np

from benchmarks.common import emit


def structural(full: bool = False):
    import subprocess
    import sys
    import os
    import textwrap
    from pathlib import Path
    repo = Path(__file__).resolve().parents[1]
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, json
        from jax.sharding import PartitionSpec as P
        from repro.distrib import homa_collectives as HC
        mesh = jax.make_mesh((8,), ("data",))
        from repro.configs.reduced import reduced_config
        from repro.models import model as M
        from repro.models.params import init_params
        cfg = reduced_config("llama3.2-3b")
        params = init_params(M.model_defs(cfg), jax.random.key(0))
        grads = jax.tree.map(lambda p: p.astype(jnp.float32), params)

        for name, scfg in [
            ("homa", HC.SyncConfig(chunk_bytes=1 << 14, overcommit=7)),
            ("homa_int8", HC.SyncConfig(chunk_bytes=1 << 14, overcommit=7,
                                        compress="int8")),
        ]:
            @jax.shard_map(mesh=mesh, in_specs=(P(),), out_specs=P(),
                           check_vma=False)
            def sync(g):
                out, _ = HC.homa_allreduce(g, "data", scfg)
                return out

            txt = jax.jit(sync).lower(grads).compile().as_text()
            nar = txt.count(" all-reduce(") + txt.count(" all-reduce-start(")
            nag = txt.count(" all-gather(") + txt.count(" all-gather-start(")
            print(json.dumps({"mode": name, "all_reduce": nar,
                              "all_gather": nag}))

        @jax.shard_map(mesh=mesh, in_specs=(P(),), out_specs=P(),
                       check_vma=False)
        def naive(g):
            return HC.naive_allreduce(g, "data")
        txt = jax.jit(naive).lower(grads).compile().as_text()
        print(json.dumps({"mode": "naive",
                          "all_reduce": txt.count(" all-reduce(")
                          + txt.count(" all-reduce-start("),
                          "all_gather": txt.count(" all-gather(")}))
    """)
    # an HLO study on 8 virtual CPU devices by design: the child never
    # touches an accelerator, which the parent process may hold
    env = {**os.environ, "PYTHONPATH": str(repo / "src"),
           "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd=repo, timeout=900)
    if r.returncode != 0:
        raise RuntimeError(f"collective_structural child failed "
                           f"(exit {r.returncode}):\n{r.stderr[-2000:]}")
    rows = []
    import json as _json
    for line in r.stdout.splitlines():
        line = line.strip()
        if line.startswith("{"):
            rows.append(_json.loads(line))
    emit("collective_structural", rows)
    return rows


def predicted(full: bool = False):
    """Simulator-predicted sync behaviour: gradient chunks as Homa messages.

    Measured finding (see EXPERIMENTS): with the simulator's Homa-style
    senders, small-tensor latency stays at slowdown ~1.0 even UNCHUNKED —
    because sender-side SRPT already reorders small tensors ahead of large
    ones. This confirms the paper's §2.2 claim ("senders need SRPT also")
    from the gradient-sync angle: the HoL catastrophe of streaming syncs
    comes from in-order senders, and either chunking (message orientation)
    or sender SRPT removes it. The makespan itself is bandwidth+straggler
    bound and schedule-invariant, as expected."""
    from repro.core.sim import SimConfig, simulate
    from repro.core.workloads import MessageTable
    from repro.distrib.homa_collectives import SyncConfig, chunk_plan
    from repro.configs.reduced import reduced_config
    from repro.models import model as M
    from repro.models.params import param_shapes, tree_map_defs
    import jax

    cfg = reduced_config("llama3.2-3b")
    shapes = [(tuple(s.shape), s.dtype) for s in
              jax.tree.leaves(param_shapes(M.model_defs(cfg)))]
    rows = []
    # A/B: message orientation. chunked = Homa-style size-bounded messages;
    # unchunked = streaming-style whole-tensor messages (the paper's
    # InfRC/TCP single-stream analogue) — the big-tensor messages HoL-block
    # the small ones. (With uniform chunk sizes SRPT-vs-FIFO is a no-op by
    # construction — measured and expected; size diversity is what makes
    # scheduling matter, which is the paper's own premise.)
    for chunked in (True, False):
        # streaming mode sends tensors in definition order (embedding first,
        # like a naive fused/streaming sync); chunked mode uses the Homa
        # SRPT plan
        plan = chunk_plan(shapes, SyncConfig(
            chunk_bytes=(1 << 13) if chunked else (1 << 30), srpt=chunked))
        n_hosts = 8
        # all-gather-style exchange: chunk i of host h goes to peer
        # (h+1+i) % H, so receiver downlinks are contended (multiple senders
        # per destination) and the issue ORDER (srpt vs fifo) is the
        # messages' arrival order. Host 0 is a straggler (sends 3000 slots
        # late) — Homa's overcommitment must keep the other downlinks busy.
        msgs = len(plan) * n_hosts
        src = np.repeat(np.arange(n_hosts), len(plan)).astype(np.int32)
        ci = np.tile(np.arange(len(plan)), n_hosts)
        dst = ((src + 1 + ci % (n_hosts - 1)) % n_hosts).astype(np.int32)
        size = np.tile([c.bytes for c in plan], n_hosts).astype(np.int64)
        # arrival order = the scheduler's issue order (2 slots per issue)
        arr = (ci * 2).astype(np.int32)
        arr[src == 0] += 3000                      # straggler
        tbl = MessageTable(src, dst, size, arr, "gradsync", 0.0, 256)
        for proto in ("homa", "basic"):
            sim = SimConfig(n_hosts=n_hosts, protocol=proto,
                            max_slots=40_000, ring_cap=4096)
            st = simulate(sim, tbl)
            done = st.done
            fin = int(st.completion[done].max()) if done.any() else -1
            # the makespan is bandwidth+straggler-bound for ANY schedule;
            # what scheduling buys is EARLY completions (first tensors
            # unblock overlapped optimizer updates) and small-message
            # latency (the paper's whole point):
            comp = np.sort(st.completion[done])
            half = int(comp[len(comp) // 2]) if len(comp) else -1
            small = done & (st.size_bytes < 2048)
            p99s = (st.percentile(99, small) or -1 if small.any() else -1)
            rows.append(dict(mode="chunked" if chunked else "unchunked",
                             protocol=proto,
                             all_done=bool(done.all()),
                             sync_slots=fin,
                             half_done_slot=half,
                             small_chunk_p99_slowdown=round(p99s, 2)))
    emit("collective_predicted", rows)
    return rows
