"""The Pallas kernels and the matfree mode-0 contractions compile for a
TPU v5e chip at the paper's tensor sizes, and fit.  The chip is
described, not attached (XLA's TPU compiler runs on the host), so this
guards Mosaic lowering and the programs' device memory on every run at no
chip time.  Nothing is executed.

Bound: a kernel's compiled scratch (``temp``) stays within twice the bytes
it reads and writes, and the whole program fits one chip's 16 GB of HBM.
Before the lane-dense views, the last-mode Gram asked for 51 GB at Cavity
and 275 GB at Boats.
"""

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import solvers, tensor_ops
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
#: the ``name=`` of each ``pallas_call`` in ``repro.kernels``
KERNEL_NAMES = ("ttm_interior", "ttt_nt", "ttt_tn", "matmul")


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
    # every kernel carries a stable name, for a profile to find it by
    assert any(k in compiled.as_text() for k in KERNEL_NAMES)
    mem = compiled.memory_analysis()
    io = 4 * (sum(math.prod(a.shape) for a in args) + math.prod(out))
    assert mem.temp_size_in_bytes <= 2 * io, (mem, io)
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < HBM_BYTES, mem


#: (program, input shapes, bound on the compiled temp, in bytes, largest
#: ``dynamic-update-slice`` result allowed, in elements): the mode-0
#: contractions on matfree, which read the input on its own axes.
#: Each temp bound sits above the reading (2.27 GB: one hoisted relayout of
#: the Boats input; 0.005 GB: EIG's Gram, ``eigh`` and projection, no copy;
#: 0) and below what a reshape to a merged view costs on the chip's tiled
#: layout, a copy of the whole input (4.30 GB; 2.15 GB; 0.84 GB).  A
#: relayout built slice by slice is a ``dynamic-update-slice`` as large as
#: the input; only ``eigh`` may update, its QDWH blocks of at most
#: (2·I_n)² elements (640² at Boats' 320, against 537.6e6 for the input).
MODE0 = {
    "als_solve Boats": (lambda y: solvers.als_solve(y, 0, 10),
                        [TENSORS["Boats"][0]], 2.4e9, 0),
    "eig_solve Boats": (lambda y: solvers.eig_solve(y, 0, 10),
                        [TENSORS["Boats"][0]], 0.1e9, 640 * 640),
    "ttm Cavity": (lambda x, u: tensor_ops.ttm(x, u, 0),
                   [TENSORS["Cavity"][0], (32, 100)], 0.05e9, 0),
}

#: result type of an HLO instruction: ``= f32[320,240,7000]{...} op(``
_HLO_RESULT = re.compile(r"= \w+\[([\d,]*)\]\S* ([\w-]+)\(")


def _update_sizes(hlo: str) -> list[int]:
    """Elements in the result of each ``dynamic-update-slice``."""
    return [math.prod(int(d) for d in dims.split(",") if d)
            for dims, op in _HLO_RESULT.findall(hlo)
            if op == "dynamic-update-slice"]


@pytest.mark.parametrize("name", sorted(MODE0))
def test_matfree_mode0_reads_the_input_on_its_own_axes(one_chip, name):
    fn, shapes, bound, largest_update = MODE0[name]
    compiled = jax.jit(fn).lower(*(_f32(s, one_chip) for s in shapes)) \
        .compile()
    text = compiled.as_text()
    if not largest_update:
        assert "dynamic-update-slice" not in text
    assert max(_update_sizes(text), default=0) <= largest_update
    assert compiled.memory_analysis().temp_size_in_bytes <= bound
