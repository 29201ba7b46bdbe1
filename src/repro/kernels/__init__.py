"""Pallas TPU kernels for the perf-critical compute layers.

Each kernel package has:
  kernel.py  - pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
  ops.py     - jit'd public wrapper (jnp path off TPU / interpret mode)
  ref.py     - pure-jnp oracle used by tests

Paper-side kernels (the scheduler's hot spots, DESIGN.md §4):
  costmap      - fused latency -> LUT perf -> integer arc cost (Eq. 6)
  auction_bid  - dense top-2 bidding reduction for the auction solver

Data-plane kernels (the scheduled workloads' hot spots):
  flash_attention   - blocked causal attention (train/prefill)
  decode_attention  - single-token GQA attention against a KV cache
  rwkv6_scan        - RWKV-6 data-dependent-decay linear recurrence
  rglru_scan        - RG-LRU gated linear recurrence (RecurrentGemma)
"""
