"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Nothing runs here: each kernel is lowered and compiled for one chip of a
described (not attached) v5e:2x2 topology, which raises whatever the
chip's compiler would refuse — block layouts, casts, fast-memory use — at
no chip time. Interpret-mode tests cannot see those refusals. Widths:
vit-base rounds (batch 16, 196 patches + CLS + a 16-token prompt, 12 heads
of 64) and qwen2.5-14b decode (40 query / 8 kv heads of 128, a
4096-token window, 8 slots, vocab 152064).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and pytest-xdist workers
all import this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import SplitConfig, SplitModel
from repro.kernels.el2n.ops import el2n_scores
from repro.kernels.flash_attention.decode import (decode_attention,
                                                  paged_decode_attention)
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.quant.ops import dequantize_int8, quantize_int8
from repro.kernels.secure_mask.ops import masked_encode, ring_size
from repro.launch.serve import served_config
from repro.runtime import WireSpec
from repro.serve.steps import make_multi_decode_step

# vit-base round: batch 16, 196 patches + CLS + 16 prompt tokens
VIT_B, VIT_S, VIT_H, VIT_DH = 16, 213, 12, 64
# qwen2.5-14b decode: 8 slots, 40 query / 8 kv heads of 128, window 4096
QW_B, QW_W, QW_HQ, QW_HKV, QW_DH, QW_VOCAB = 8, 4096, 40, 8, 128, 152064
PAGE = 16


@pytest.fixture(scope="module")
def topo():
    """A v5e:2x2 described for the compiler, with the persistent
    compilation cache off: an entry compiled for a described chip cannot
    be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def compile_kernel(one_chip, fn, *shapes, name):
    """Compile `fn` on shapes placed on one described chip; assert the
    Pallas kernel `name` is in the program (no silent XLA fallback)."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo and name in hlo, name


def test_flash_forward_vit_base(one_chip):
    qkv = ((VIT_B, VIT_S, VIT_H, VIT_DH), jnp.float32)
    compile_kernel(
        one_chip,
        lambda q, k, v: flash_attention(q, k, v, causal=False,
                                        impl="pallas"),
        qkv, qkv, qkv, name="sfprompt_flash_attention")


@pytest.mark.parametrize("vocab", [100, QW_VOCAB])
def test_el2n(one_chip, vocab):
    compile_kernel(
        one_chip, lambda lg, y: el2n_scores(lg, y, impl="pallas"),
        ((VIT_B, vocab), jnp.float32), ((VIT_B,), jnp.int32),
        name="sfprompt_el2n")


# the vit-base smashed rows of one round batch, and batch-1 prefills whose
# row counts are not multiples of 8
@pytest.mark.parametrize("rows", [VIT_B * VIT_S, 12, 20])
def test_int8_quant_dequant(one_chip, rows):
    x = ((rows, 768), jnp.float32)
    compile_kernel(one_chip,
                   lambda x, u: quantize_int8(x, u, impl="pallas"), x, x,
                   name="sfprompt_wire_quantize")
    compile_kernel(one_chip,
                   lambda v, s: dequantize_int8(v, s, impl="pallas"),
                   ((rows, 768), jnp.int8), ((rows, 1), jnp.float32),
                   name="sfprompt_wire_dequantize")


def test_decode_attention_qwen_geometry(one_chip):
    kv = ((QW_B, QW_W, QW_HKV, QW_DH), jnp.float32)
    compile_kernel(
        one_chip,
        lambda q, k, v, qp, kp: decode_attention(
            q, k, v, q_positions=qp, kv_positions=kp, impl="pallas"),
        ((QW_B, 1, QW_HQ, QW_DH), jnp.float32), kv, kv,
        ((QW_B,), jnp.int32), ((QW_B, QW_W), jnp.int32),
        name="sfprompt_decode_attention")


def test_paged_decode_attention_qwen_geometry(one_chip):
    n_pages = 2 + QW_B * QW_W // PAGE
    pool = ((n_pages, PAGE, QW_HKV, QW_DH), jnp.float32)
    compile_kernel(
        one_chip,
        lambda q, k, v, bt, qp, kp: paged_decode_attention(
            q, k, v, block_tables=bt, q_positions=qp, kv_positions=kp,
            impl="pallas"),
        ((QW_B, 1, QW_HQ, QW_DH), jnp.float32), pool, pool,
        ((QW_B, QW_W // PAGE), jnp.int32), ((QW_B,), jnp.int32),
        ((n_pages, PAGE), jnp.int32),
        name="sfprompt_paged_decode_attention")


def test_secure_mask_vit_base_upload(one_chip):
    """One client's masked upload of the vit-base (tail, prompt)."""
    model = SplitModel(get_config("vit-base"),
                       SplitConfig(head_cycles=1, tail_cycles=1,
                                   prompt_len=16))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    n = ring_size(sum(x.size for x in jax.tree.leaves(
        {"tail": params["tail"], "prompt": params["prompt"]})))
    assert n > 7_000_000
    compile_kernel(
        one_chip, lambda x, s, g: masked_encode(x, s, g, impl="pallas"),
        ((n,), jnp.float32), ((3,), jnp.uint32), ((3,), jnp.int32),
        name="sfprompt_secure_masked_encode")


def test_served_decode_step_qwen_published_widths(one_chip):
    """The serve launcher's 8-token decode program at qwen2.5-14b's
    published widths, depth cut to 3 layers, 1 tenant, 4 slots and a
    512-token window: it runs the decode and wire kernels and fits one
    chip's 16 GB."""
    model = SplitModel(
        served_config("qwen2.5-14b", reduced=False, layers=3),
        SplitConfig(head_cycles=1, tail_cycles=1, prompt_len=4),
        WireSpec.make("int8", impl="pallas"))
    place = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        tree)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    shared = place({"head": params["head"], "body": params["body"]})
    tails = place(jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((1,) + s.shape, s.dtype),
        params["tail"]))
    cache = place(jax.eval_shape(lambda: model.init_cache(4, seq_len=512)))
    ids = jax.ShapeDtypeStruct((4,), jnp.int32, sharding=one_chip)
    step = make_multi_decode_step(model, 8, impl="pallas")
    compiled = jax.jit(step, donate_argnums=(6,)).lower(
        shared, tails, ids, ids, ids, ids, cache).compile()
    hlo = compiled.as_text()
    for name in ("sfprompt_decode_attention", "sfprompt_wire_quantize"):
        assert name in hlo, name
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert used < 12e9, used
