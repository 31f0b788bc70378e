"""Compile the device kernels for a described TPU v5e chip, without one.

The TPU compiler ships with jaxlib, and it compiles for a chip that is
described rather than attached.  So every kernel the chip would run is
compiled here at the real sizes of the configurations the repo ships: the
population engine's label kernel at the zoo graphs' sizes (the scheduler's
main path), and each Pallas kernel at its default block sizes.  A refusal
(tiling, VMEM, an unsupported op) fails here instead of on the chip.

Nothing runs: these tests say nothing about results or times.  The topology
is described inside a fixture, never at import, because only one process at
a time may load the TPU library; and the persistent compilation cache is off
around the compiles, since an entry written for a described chip cannot be
read back without one.
"""
import os

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from repro.core.population import StaticTables, label_kernel, label_tables  # noqa: E402
from repro.search.registry import build_workload  # noqa: E402

#: the paper's population (P=100), padded as the engine pads it
PADDED_P = 112


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("workload,n,m", [
    ("mobilenet_v3", 92, 109),
    ("resnet50", 73, 88),
    ("unet", 36, 39),
])
def test_label_kernel_compiles_at_zoo_sizes(one_chip, workload, n, m):
    t = StaticTables(build_workload(workload).compiled())
    assert (t.n, t.m) == (n, m)
    args = [_shape((PADDED_P, m), jnp.uint8, one_chip)]
    args += [_shape(a.shape, a.dtype, one_chip) for a in label_tables(t)]
    compiled = label_kernel().lower(*args).compile()
    out = compiled.out_info
    assert out.shape == (PADDED_P, n) and out.dtype == jnp.int32



def test_label_kernel_fuses_its_jump_at_n_1024(one_chip):
    """The kernel has one path for every graph size.  Its largest
    intermediate, the pointer jump's ``n x n x P`` int32 select (512 MiB
    at n = 1024, P = 128), must stay fused into its reduction: the
    compiled program stores no temporary of that size."""
    from repro.core.graph import Layer, LayerGraph
    n, p = 1024, 128
    g = LayerGraph("chain1024")
    names = []
    for i in range(n):
        ins = [names[i - 1]] if i else []
        if i >= 16 and i % 8 == 0:
            ins.append(names[i - 16])             # a skip edge every 8 nodes
        names.append(g.add(Layer(name=f"l{i}", kind="add"), ins))
    t = StaticTables(g.compiled())
    args = [_shape((p, t.m), jnp.uint8, one_chip)]
    args += [_shape(a.shape, a.dtype, one_chip) for a in label_tables(t)]
    compiled = label_kernel().lower(*args).compile()
    assert compiled.out_info.shape == (p, n)
    assert compiled.memory_analysis().temp_size_in_bytes < n * n * p * 4

def test_flash_attention_compiles_at_qwen2_7b_width(one_chip):
    from repro.kernels import flash_attention
    # qwen2-7b: 28 query heads, 4 kv heads (GQA), head_dim 128, bf16
    q = _shape((1, 2048, 28, 128), jnp.bfloat16, one_chip)
    kv = _shape((1, 2048, 4, 128), jnp.bfloat16, one_chip)
    compiled = flash_attention.lower(q, kv, kv, causal=True).compile()
    assert compiled.out_info.shape == (1, 2048, 28, 128)
    assert "tpu_custom_call" in compiled.as_text()


def test_rmsnorm_compiles_at_qwen2_7b_width(one_chip):
    from repro.kernels import fused_rmsnorm
    x = _shape((2048, 3584), jnp.bfloat16, one_chip)
    w = _shape((3584,), jnp.bfloat16, one_chip)
    compiled = fused_rmsnorm.lower(x, w).compile()
    assert compiled.out_info.shape == (2048, 3584)
    assert "tpu_custom_call" in compiled.as_text()


#: the scan kernels index a loaded (time_chunk, block) tile with the fori_loop
#: counter, a dynamic slice of a value that Mosaic does not lower; the cause
#: is the kernel body, not its tiling or block sizes
_SCAN_REFUSED = pytest.mark.xfail(
    raises=NotImplementedError, strict=True,
    reason="Unimplemented primitive in Pallas TPU lowering for "
           "KernelType.TC: dynamic_slice")


@_SCAN_REFUSED
def test_mamba_scan_compiles_at_falcon_mamba_7b_width(one_chip):
    from repro.kernels import mamba_scan
    # falcon-mamba-7b: d_inner = 2 x 4096, ssm_state 16
    da = _shape((1, 512, 8192, 16), jnp.float32, one_chip)
    c = _shape((1, 512, 16), jnp.float32, one_chip)
    compiled = mamba_scan.lower(da, da, c).compile()
    assert compiled.out_info.shape == (1, 512, 8192)
    assert "tpu_custom_call" in compiled.as_text()


@_SCAN_REFUSED
def test_rglru_scan_compiles_at_recurrentgemma_2b_width(one_chip):
    from repro.kernels import rglru_scan
    a = _shape((1, 2048, 2560), jnp.float32, one_chip)
    compiled = rglru_scan.lower(a, a).compile()
    assert compiled.out_info.shape == (1, 2048, 2560)
    assert "tpu_custom_call" in compiled.as_text()
