"""The Pallas kernels compile for a TPU v5e chip at the paper's tensor
sizes, and fit.  The chip is described, not attached (XLA's TPU compiler
runs on the host), so this guards Mosaic lowering and the kernels' device
memory on every run at no chip time.  Nothing is executed.

Bound: a kernel's compiled scratch (``temp``) stays within twice the bytes
it reads and writes, and the whole program fits one chip's 16 GB of HBM.
Before the lane-dense views, the last-mode Gram asked for 51 GB at Cavity
and 275 GB at Boats.
"""

import math
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops

HBM_BYTES = 16 << 30

#: (tensor, shape, ranks) from the paper's Table III
TENSORS = {
    "Cavity": ((100, 100, 10000), (20, 20, 20)),
    "Boats": ((320, 240, 7000), (10, 10, 10)),
    "Air": ((30648, 376, 6), (10, 10, 5)),
    "MNIST": ((784, 5000, 10), (65, 142, 10)),
}
CASES = [("Cavity", 0), ("Cavity", 1), ("Cavity", 2), ("Boats", 2),
         ("Air", 2), ("MNIST", 1)]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache, so keep it out of one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _f32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


@pytest.mark.parametrize("op", ["gram", "ttm", "ttt"])
@pytest.mark.parametrize("name,mode", CASES)
def test_kernel_compiles_and_fits(one_chip, name, mode, op):
    shape, ranks = TENSORS[name]
    r = ranks[mode]
    x = _f32(shape, one_chip)
    small = shape[:mode] + (r,) + shape[mode + 1:]
    if op == "gram":
        fn, args, out = (lambda x: ops.gram(x, mode, interpret=False),
                         (x,), (shape[mode], shape[mode]))
    elif op == "ttm":
        fn, args, out = (lambda x, u: ops.ttm(x, u, mode, interpret=False),
                         (x, _f32((r, shape[mode]), one_chip)), small)
    else:
        fn, args, out = (lambda x, y: ops.ttt(x, y, mode, interpret=False),
                         (x, _f32(small, one_chip)), (shape[mode], r))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    io = 4 * (sum(math.prod(a.shape) for a in args) + math.prod(out))
    assert mem.temp_size_in_bytes <= 2 * io, (mem, io)
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < HBM_BYTES, mem
