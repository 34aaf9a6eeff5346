"""Pallas TPU kernels for the framework's compute hot-spots.

Each kernel package is <name>/{kernel.py, ops.py, ref.py}:
  kernel.py — pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
  ops.py    — jit'd public wrapper in model layout
  ref.py    — pure-jnp oracle (tests assert_allclose against it)

Kernels:
  flash_attention — online-softmax attention; deletes the (b,h,s,chunk)
                    f32 score traffic that dominates the dry-run memory
                    roofline for attention archs.
  rwkv6_wkv       — chunked WKV6 linear recurrence (data-dependent decay).
  moe_mlp         — fused per-expert SwiGLU over MoE capacity blocks;
                    d_ff intermediates never reach HBM.
  quantize        — int8 block quantization (gradient compression).

Every wrapper compiles for the TPU by default (``interpret=False``).
The tests run each kernel with ``interpret=True`` on the CPU against its
``ref.py`` oracle, and ``tests/test_tpu_compile.py`` compiles each one
for a described TPU v5e chip at model widths.  No model path calls a
kernel yet: the dry-run and the served/trained models are plain XLA.
"""
