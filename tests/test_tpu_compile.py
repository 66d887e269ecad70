"""Compile the main-path kernels for a TPU v5e chip that is described,
not attached: the chip's compiler refuses tilings, layouts and scoped
VMEM use that interpret mode accepts, so these tests catch on the CPU
what would otherwise fail only on the chip.

Widths are a deployment's: k=256 2-bit codes (W=16 words per row),
Q=256 queries, N=2^20 corpus rows, rerank_m=1024, top_k=10, each kernel
at its default block sizes. Each test asserts the kernel reached the
compiled program as a Mosaic custom call.

The topology is described inside a module-scoped fixture (never at
import): only the worker that runs this file loads the TPU compiler.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.schemes import CodeSpec
from repro.kernels.encode_fused import encode_fused_pallas
from repro.kernels.fused_scored import fused_scored_topk_masked_pallas
from repro.kernels.pack_codes import pack_codes_pallas
from repro.kernels.packed_collision import packed_topk_masked_pallas
from repro.kernels.packed_linear import (packed_linear_bwd_pallas,
                                         packed_linear_fwd_pallas)

K, BITS, W, Q, N, D = 256, 2, 16, 256, 1 << 20, 100
RERANK_M, TOP_K = 1024, 10
FP = W * (32 // BITS) * (1 << BITS)
SPEC = CodeSpec("2bit", 0.75)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # noqa: BLE001 — any failure means "absent"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip cannot be read back without one:
    keep it out of any persistent compilation cache."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


VALID = ((N + 31) // 32,)
CASES = {
    "encode_fused": (lambda x, r: encode_fused_pallas(x, r, SPEC),
                     [((4096, D), jnp.float32), ((D, K), jnp.float32)]),
    "pack_codes": (lambda c: pack_codes_pallas(c, BITS),
                   [((4096, K), jnp.int32)]),
    "packed_topk_masked": (
        lambda q, db, v: packed_topk_masked_pallas(q, db, v, BITS, K, TOP_K),
        [((Q, W), jnp.uint32), ((N, W), jnp.uint32), (VALID, jnp.uint32)]),
    "fused_scored_topk_masked_f32": (
        lambda q, t, db, v: fused_scored_topk_masked_pallas(
            q, t, db, v, BITS, K, RERANK_M, TOP_K),
        [((Q, W), jnp.uint32), ((Q, FP), jnp.float32),
         ((N, W), jnp.uint32), (VALID, jnp.uint32)]),
    "fused_scored_topk_masked_int8": (
        lambda q, t, db, v, s: fused_scored_topk_masked_pallas(
            q, t, db, v, BITS, K, RERANK_M, TOP_K, scales=s),
        [((Q, W), jnp.uint32), ((Q, FP), jnp.int8), ((N, W), jnp.uint32),
         (VALID, jnp.uint32), ((Q, W), jnp.float32)]),
    "packed_linear_fwd": (
        lambda t, db: packed_linear_fwd_pallas(t, db, BITS),
        [((2, FP), jnp.float32), ((N, W), jnp.uint32)]),
    "packed_linear_bwd": (
        lambda g, db: packed_linear_bwd_pallas(g, db, BITS),
        [((2, N), jnp.float32), ((N, W), jnp.uint32)]),
}


@pytest.mark.parametrize("kernel", sorted(CASES))
def test_kernel_compiles_for_v5e(kernel, one_chip, no_persistent_cache):
    fn, shapes = CASES[kernel]
    _compile(fn, shapes, one_chip)
