"""Compiles for a described TPU v5e chip, at model widths, with no chip.

The TPU compiler is installed with jax; it compiles for a chip that is
described (``topologies.get_topology_desc``) and not attached, and
refuses what the chip would refuse: kernel tilings Mosaic cannot lower,
programs that do not fit the device's memory.  Nothing runs, so these
tests say nothing about results or times.

The topology is described inside a module-scoped fixture only: the TPU
library may be loaded by one process at a time, so describing it while
a module is imported would break the test workers that import it.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.moe_mlp.ops import expert_mlp
from repro.kernels.quantize.ops import quantize
from repro.kernels.rwkv6_wkv.ops import wkv6
from repro.models import build_model
from repro.serve.step import build_decode_step

HBM_BYTES = 16e9          # one TPU v5e chip (Google Cloud, "TPU v5e")
SEQ = 4096                # kernel sequence length


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else libtpu logs to /tmp
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means no TPU compiler
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _kernel_case(name):
    """(jitted kernel wrapper, [(shape, dtype)]) at published widths."""
    bf16, f32 = jnp.bfloat16, jnp.float32
    if name == "flash_attention":
        c = get_config("stablelm-1.6b")
        return flash_attention, [((1, SEQ, c.n_heads, c.head_dim), bf16)] * 3
    if name == "wkv6":
        c = get_config("rwkv6-7b")
        h, n = c.n_rwkv_heads, c.rwkv_head_size
        return wkv6, [((1, SEQ, h, n), bf16)] * 4 + [((h, n), f32)]
    if name == "expert_mlp":
        c = get_config("olmoe-1b-7b")
        e, d, f = c.n_experts, c.d_model, c.d_ff
        return expert_mlp, [((1, e, 256, d), bf16), ((e, d, f), bf16),
                            ((e, d, f), bf16), ((e, f, d), bf16)]
    # quantize: one stablelm-1.6b MLP weight's gradient
    c = get_config("stablelm-1.6b")
    return quantize, [((c.d_model * c.d_ff,), f32)]


@pytest.mark.parametrize("name", ["flash_attention", "wkv6", "expert_mlp",
                                  "quantize"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = _kernel_case(name)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = fn.lower(*args).compile()       # interpret=False by default
    assert "tpu_custom_call" in compiled.as_text()


def _decode_step_bytes(one_chip, model, slots, capacity):
    """What the compiler says the server's decode step holds, compiled on
    the caller's f32 params: an upper bound, as ``BatchServer`` holds
    them cast once to bf16."""
    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    params, _ = model.param_specs()
    batch = {"tokens": jax.ShapeDtypeStruct((slots, 1), jnp.int32),
             "cache": model.cache_spec(slots, capacity),
             "cur_len": jax.ShapeDtypeStruct((slots,), jnp.int32)}
    compiled = jax.jit(build_decode_step(model)).lower(
        on_chip(params), on_chip(batch)).compile()
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


def test_stablelm_decode_step_fits_one_v5e(one_chip):
    """The server's decode step at 4 slots x 1024 capacity."""
    total = _decode_step_bytes(
        one_chip, build_model(get_config("stablelm-1.6b")), 4, 1024)
    assert total <= HBM_BYTES, total


def test_granite_stage_decode_step_fits_one_v5e(one_chip):
    """One pipeline stage of granite-4.0-h-micro (20 of 40 layers) at the
    benchmark cell's 32 slots x 4096: KV of 2 attention layers and 18
    Mamba-2 states per slot beside the f32 params."""
    cfg = dataclasses.replace(get_config("granite-4.0-h-micro"), n_layers=20)
    total = _decode_step_bytes(one_chip, build_model(cfg), 32, 4096)
    assert total <= HBM_BYTES, total
