"""Test traffic kind ``shift``: the Poisson table of ``bench/gen.py``
with every message sent ``shift`` hosts on from its source, ``shift``
(1 to ``n_hosts - 1``) drawn per table from its stream."""
import numpy as np

from bench import gen


def table(mix, n_hosts, slot_bytes, rng):
    t = gen.poisson_table({**mix, "kind": "poisson"}, n_hosts, slot_bytes,
                          rng)
    shift = int(rng.integers(1, n_hosts))
    t["dst"] = ((t["src"] + shift) % n_hosts).astype(np.int32)
    return t
