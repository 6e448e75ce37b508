"""Compile for a described TPU v5e, with no chip attached: the Pallas
kernels at published shapes and qwen2.5-3b's serving steps at full
width. What the chip's compiler refuses fails here, at no chip time.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and every test worker
imports this file."""

from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # compiles for a described chip cannot be read back from the
    # persistent cache without one: keep them out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _kernel_cases():
    from repro.kernels.decode_attention.ops import decode_attention
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.int8_matmul.ops import int8_matmul
    from repro.kernels.planner_argmax.ops import masked_argmax
    from repro.kernels.rglru_scan.ops import rglru_scan
    from repro.kernels.rwkv6_scan.ops import wkv6

    S = jax.ShapeDtypeStruct
    bf, f32 = jnp.bfloat16, jnp.float32
    return {
        # qwen2.5-3b: 16 query / 2 KV heads of 128, d_model 2048, d_ff 11008
        "decode_attention": (decode_attention, (
            S((2, 1, 16, 128), bf), S((2, 4096, 2, 128), bf),
            S((2, 4096, 2, 128), bf), S((2,), jnp.int32))),
        "flash_attention": (flash_attention, (
            S((1, 2048, 16, 128), bf), S((1, 2048, 2, 128), bf),
            S((1, 2048, 2, 128), bf))),
        "int8_matmul": (int8_matmul, (
            S((16, 2048), bf), S((2048, 11008), jnp.int8), S((11008,), f32))),
        # recurrentgemma-2b: lru_width 2560
        "rglru_scan": (rglru_scan, (
            S((2, 2048, 2560), f32), S((2, 2048, 2560), f32),
            S((2, 2560), f32))),
        # rwkv6-3b: 40 heads of 64
        "rwkv6_scan": (wkv6, (S((1, 40, 512, 64), f32),) * 4
                       + (S((40, 64), f32),)),
        # the planner's worst-fit reduction at 10k servers
        "planner_argmax": (partial(masked_argmax, impl="pallas"), (
            S((10_000,), f32), S((10_000,), jnp.bool_))),
    }


@pytest.mark.parametrize("name", ["decode_attention", "flash_attention",
                                  "int8_matmul", "rglru_scan", "rwkv6_scan",
                                  "planner_argmax"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _kernel_cases()[name]
    compiled = jax.jit(fn).lower(*_on(one_chip, args)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_planner_argmax_compiles_under_x64(one_chip):
    """The jax planner backend traces the kernel under enable_x64: every
    constant and index map must stay 32-bit for Mosaic."""
    from repro.kernels.planner_argmax.ops import masked_argmax
    args = (jax.ShapeDtypeStruct((10_000,), jnp.float32),
            jax.ShapeDtypeStruct((10_000,), jnp.bool_))
    with jax.enable_x64(True):
        compiled = jax.jit(partial(masked_argmax, impl="pallas")).lower(
            *_on(one_chip, args)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def qwen():
    from repro import configs
    from repro.models import model as MDL
    cfg = configs.get_config("qwen2.5-3b")
    return cfg, MDL.param_shapes(cfg)


def test_full_width_decode_step_compiles_for_v5e(one_chip, qwen):
    from repro.models import model as MDL
    cfg, params = qwen
    cache = jax.eval_shape(partial(MDL.init_cache, cfg, 2, 96))
    tok = jax.ShapeDtypeStruct((2,), jnp.int32)
    step = jax.jit(lambda p, c, t: MDL.decode_step(p, cfg, t, c))
    compiled = step.lower(*_on(one_chip, (params, cache, tok))).compile()
    mem = compiled.memory_analysis()
    # the bf16 weights alone are 6.17 GB: all of it fits one 16 GB chip
    assert 6e9 < mem.argument_size_in_bytes < 7e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def test_full_width_prefill_compiles_for_v5e(one_chip, qwen):
    from repro.models import model as MDL
    cfg, params = qwen
    cache = jax.eval_shape(partial(MDL.init_cache, cfg, 1, 96))
    prompt = jax.ShapeDtypeStruct((1, 8), jnp.int32)    # engine bucket
    step = jax.jit(lambda p, c, t: MDL.prefill(p, cfg, t, c))
    compiled = step.lower(*_on(one_chip, (params, cache, prompt))).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2e9


def test_full_width_layer_init_fits_beside_its_output(one_chip, qwen):
    """`init_params` builds the 36-layer stack in one program whose
    scratch stays far below the stack it writes: no second copy."""
    from repro.models import model as MDL
    cfg, params = qwen
    keys = jax.ShapeDtypeStruct((cfg.num_layers, 2), jnp.uint32)
    compiled = MDL._init_stack.lower(
        _on(one_chip, keys), cfg=cfg, kind="global").compile()
    mem = compiled.memory_analysis()
    stack = sum(x.size * x.dtype.itemsize
                for x in jax.tree_util.tree_leaves(params["cycles"]))
    assert stack <= mem.output_size_in_bytes < 1.01 * stack   # tile pad
    assert mem.temp_size_in_bytes < 0.25 * stack
