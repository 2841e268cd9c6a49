"""Pallas TPU kernels for the compute hot spots ABC serves.

Each kernel package ships:
  kernel.py — ``pl.pallas_call`` + explicit BlockSpec VMEM tiling (TPU target)
  ops.py    — jit-friendly wrapper with an XLA (chunked pure-jnp) fallback;
              ``repro.kernels.config`` picks the Pallas path on a TPU backend
              and the XLA path on any other (the CPU tests, the dry-run)
  ref.py    — naive pure-jnp oracle used by the allclose test sweeps

Kernels: flash_attention (prefill), decode_attention (GQA single-token and
paged), mamba2_ssd (chunked state-space dual), rwkv6_wkv (data-dependent-
decay linear attention), agreement (ABC's ensemble vote/score reduce),
compaction (deferral mask to dense payload).
"""
from repro.kernels import config

__all__ = ["config"]
