"""The main path's Pallas kernels and the proxy trainer, compiled for a
TPU v5e that is described, not attached.

The TPU compiler is installed with jaxlib's TPU support, so these run on
a CPU-only machine: each test lowers and compiles at real widths, which
raises whatever the chip's compiler (Mosaic for the kernels) would
refuse, and asserts that the kernel is in the compiled program. Nothing
runs, so this says nothing about results or times.

The topology is described inside a fixture, never at import, so test
collection is the same in every worker process; where it cannot be
described the tests skip. The persistent compilation cache is off
around the compiles: an entry written for a described chip cannot be
read back without one.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

DEFAULT_WIDTHS = (4096, 512, 128)      # ProxyConfig defaults (D, H, L)
SMOLLM_WIDTHS = (960, 512, 128)        # a smollm-360m store (d_model 960)
TILE = 8192                            # ScoringExecutor's default chunk


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def _mlp_specs(sharding, d, h, l):
    return [_spec(sharding, s) for s in
            [(TILE, d), (d, h), (h,), (h, h), (h,), (h, l), (l,)]]


@pytest.mark.parametrize("widths", [DEFAULT_WIDTHS, SMOLLM_WIDTHS])
def test_fused_scores_multi_compiles(one_chip, widths):
    from repro.kernels.fused_scoring.scoring import fused_scores_multi
    d, h, l = widths
    compiled = fused_scores_multi.lower(
        *_mlp_specs(one_chip, d, h, l), _spec(one_chip, (3, l))).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("widths", [DEFAULT_WIDTHS, SMOLLM_WIDTHS])
def test_fused_scores_single_query_compiles(one_chip, widths):
    from repro.kernels.fused_scoring.scoring import fused_scores
    d, h, l = widths
    compiled = fused_scores.lower(
        *_mlp_specs(one_chip, d, h, l), _spec(one_chip, (l,))).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("n,p", [(128, 64), (512, 256)])
def test_contrastive_losses_compile(one_chip, n, p):
    from repro.kernels.contrastive.contrastive import contrastive_losses
    compiled = contrastive_losses.lower(
        _spec(one_chip, (p,)), _spec(one_chip, (n, p)),
        _spec(one_chip, (n,)), 0.07, 0.2).compile()
    _assert_kernel(compiled)


def test_vmapped_proxy_trainer_compiles_with_kernel(one_chip):
    """The engine's training program: TRAIN_BATCH_PAD leaves vmapped over
    the scanned two-phase run at ProxyConfig defaults, phase-2 forward
    on the contrastive kernel, over a 16384-row padded sample."""
    from repro.config.base import ProxyConfig
    from repro.core import trainer
    from repro.core.encoder import encoder_init
    from repro.engine.engine import TRAIN_BATCH_PAD

    cfg = ProxyConfig(contrastive_impl="kernel")
    q, n, d = TRAIN_BATCH_PAD, 16384, cfg.embed_dim
    fn = trainer._compiled_trainer(cfg, trainer._proxy_opt_cfg(cfg),
                                   "two_phase", cfg.batch_size, multi=True,
                                   donate=True)
    keys = jax.ShapeDtypeStruct((q, 2), jnp.uint32)
    params = jax.eval_shape(
        jax.vmap(lambda k: encoder_init(k, cfg)), keys)
    args = (params, keys, jax.ShapeDtypeStruct((q, d), jnp.float32),
            jax.ShapeDtypeStruct((q, n, d), jnp.float32),
            jax.ShapeDtypeStruct((q, n), jnp.float32),
            jax.ShapeDtypeStruct((q,), jnp.int32))
    args = jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype), args)
    _assert_kernel(fn.lower(*args).compile())


def test_device_batch_row_writer_compiles_in_place(one_chip):
    """The trainer's device batch: one host block written into the
    cold cell's (TRAIN_BATCH_PAD, 16384, 4096) f32 buffer, which the
    program updates in place (its output aliases the donated input)."""
    from repro.core import trainer
    from repro.engine.engine import TRAIN_BATCH_PAD

    q, n, d = TRAIN_BATCH_PAD, 16384, 4096
    compiled = trainer._write_rows.lower(
        _spec(one_chip, (q, n, d)), _spec(one_chip, (trainer.ROW_BLOCK, d)),
        _spec(one_chip, (), jnp.int32),
        _spec(one_chip, (), jnp.int32)).compile()
    assert "input_output_alias" in compiled.as_text()
