"""Pallas TPU kernels for the compute hot-spots of the SFPrompt system.

Each kernel lives in its own subpackage:
  <name>/kernel.py  — pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
  <name>/ops.py     — jit'd public wrapper with impl dispatch (ref|pallas|interpret)
  <name>/ref.py     — pure-jnp oracle

Kernels:
  flash_attention — blockwise attention: GQA, causal, sliding window, logit softcap
  el2n            — fused EL2N score + CE over vocab tiles (paper's pruning hot-spot)
  rwkv6_scan      — RWKV-6 data-dependent-decay recurrence, chunked (GLA form)
  mamba2_scan     — Mamba-2 SSD chunked scan (matmul form for the MXU)
  quant           — int8 stochastic quantize/dequantize for the wire codecs
  secure_mask     — fused fixed-point encode + pairwise PRG mask-add for
                    masked secure aggregation (privacy engine)
"""


def row_blocks(n_rows: int, want: int) -> tuple[int, int]:
    """(block rows, padded row count) for a kernel tiled over rows.

    The TPU lowering takes a block whose row count is a multiple of 8
    sublanes or equal to the whole array's. So rows that fit in `want`
    form one block, and longer arrays tile in `want`-row blocks (a
    multiple of 8) with the row count padded up to a whole block."""
    assert want % 8 == 0, want
    if n_rows <= want:
        return n_rows, n_rows
    return want, -(-n_rows // want) * want
