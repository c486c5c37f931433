"""Compile the arbiter kernels for a TPU v5e that is described, not
attached: the TPU compiler refuses what interpret mode happily runs
(rank-1 blocks narrower than 128 lanes, ``argmin`` on int32, ``cumsum``,
scatters, batched blocks that split the sublane tile, too much VMEM).

Every compile passes ``interpret=False`` explicitly and asserts that the
program holds the Pallas kernel (``tpu_custom_call``). The topology is
described inside a fixture, never at import, so every test worker
collects the same tests and only the one that runs this file loads the
TPU library.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.arbiter import dispatch


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # these compiles could be written to the persistent cache but never
    # read back without a chip: keep the cache off around them
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        jax.config.update("jax_enable_compilation_cache", enabled)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _shape(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _drain(sharding, rows, cap, batch=()):
    return (_shape(sharding, batch + (rows, cap)),
            _shape(sharding, batch + (rows, cap)),
            _shape(sharding, batch + (rows, cap), jnp.bool_))


def _compile(fn, *args, kernel):
    """Compile for the described chip; returns the names of the Pallas
    kernels in the program, which must include ``kernel``."""
    lowered = jax.jit(fn).lower(*args)
    names = set(re.findall(r'kernel_name = "([^"]+)"', lowered.as_text()))
    assert kernel in names, names
    assert "tpu_custom_call" in lowered.compile().as_text()
    return names


@pytest.mark.parametrize("rows,cap", [(144, 4096), (16, 1024)])
def test_arbitrate_compiles(one_chip, rows, cap):
    _compile(lambda p, s, e: dispatch.pallas_arbitrate(
        p, s, e, interpret=False), *_drain(one_chip, rows, cap),
        kernel="priority_arbiter")


def test_topk_compiles(one_chip):
    _compile(lambda k: dispatch.pallas_topk(k, 7, interpret=False),
             _shape(one_chip, (144, 6000)), kernel="srpt_topk")


# the fused_speed fabric: 16 hosts, 4 racks at 2:1 -> 8 uplink rows
FUSED16 = dict(rows=16, cap=512, up_rows=8, up_cap=256, msgs=1200, K=4)


def _fused(sizes, sharding, batch=()):
    K = sizes["K"]

    def slot(*a):
        return dispatch.fused_slot(down=a[0:3], up=a[3:6], topk=(a[6], K),
                                   interpret=False)

    args = (*_drain(sharding, sizes["rows"], sizes["cap"], batch),
            *_drain(sharding, sizes["up_rows"], sizes["up_cap"], batch),
            _shape(sharding, batch + (sizes["rows"], sizes["msgs"])))
    return (jax.vmap(slot) if batch else slot), args


def test_fused_slot_compiles(one_chip):
    fn, args = _fused(FUSED16, one_chip)
    # the whole slot is one kernel: no staged fallback
    assert _compile(fn, *args, kernel="fused_slot") == {"fused_slot"}


def test_fused_slot_batched_compiles(one_chip):
    """vmap(fused_slot) -> the custom_vmap rule's grid=(B,) kernel."""
    fn, args = _fused(FUSED16, one_chip, batch=(4,))
    _compile(fn, *args, kernel="fused_slot_batch")


def _limit_sizes():
    """A paper-width slot (144 rows, K=7, the 8,192-message key cap)
    whose operands, as ``dispatch.fused_slot`` counts them, come as close
    to ``FUSED_VMEM_LIMIT_BYTES`` as 128-column steps allow."""
    rows, msgs, K = 144, 8192, 7
    keys = 4 * rows * msgs + 8 * rows * K
    cap = (dispatch.FUSED_VMEM_LIMIT_BYTES - keys) // (2 * 12 * rows)
    sizes = dict(rows=rows, cap=cap // 128 * 128, up_rows=rows,
                 up_cap=cap // 128 * 128, msgs=msgs, K=K)
    assert dispatch.fused_operand_bytes(
        down=(rows, sizes["cap"]), up=(rows, sizes["up_cap"]),
        keys=(rows, msgs), K=K) <= dispatch.FUSED_VMEM_LIMIT_BYTES
    return sizes


@pytest.mark.parametrize("batch", [(), (2,)], ids=["single", "batched"])
def test_fused_limit_compiles(one_chip, batch):
    """The fallback threshold rests on this compile: a slot at the limit
    fits the v5e's VMEM in both the single and the batched form."""
    fn, args = _fused(_limit_sizes(), one_chip, batch)
    _compile(fn, *args,
             kernel="fused_slot_batch" if batch else "fused_slot")
