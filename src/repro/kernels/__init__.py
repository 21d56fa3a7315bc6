"""Pallas TPU kernels for the perf-critical compute layers.

Each kernel package has kernel.py (pl.pallas_call + explicit BlockSpec VMEM
tiling), ops.py (jit'd dispatch wrapper), and ref.py (pure-jnp oracle).
Validated in interpret mode on CPU; compiled natively on TPU.

  flash_attention  — GQA causal/windowed prefill+train attention
  decode_attention — single-token KV-cache attention (serving hot loop)
  mamba_scan       — blocked Mamba-1 selective scan (falcon-mamba)
  rglru            — blocked RG-LRU recurrence (recurrentgemma)
  temporal_gate    — fused R2E-VID gating cell (paper Eq. 5-6)
  ccg_master       — masked CCG master step (paper Alg. 2 MP1, unrolled solver)
  ccg_encode       — fused per-task CCG encoding (accuracy -> feasibility
                     bitmask -> recourse slab, table-free routing hot path)
  ccg_solve        — fully fused CCG solver: encode -> master/SP alternation
                     -> η updates across all iterations in one kernel call
  c6_tail          — fused C6 bandwidth-repair tail (per-round demotion
                     candidates: draw, accuracies, reclaimable gain)

See README.md in this directory for the kernel-family map and the
ref-vs-Pallas dispatch rules (``force=`` pins).  Import each entry point from
its package's ``ops`` module: this package re-exports nothing, so importing
``repro.kernels.dispatch`` pulls in no kernel.
"""
