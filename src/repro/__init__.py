"""RACE-IT in JAX: analog-IMC-faithful multi-pod transformer framework.

Layers:
  core/     — the paper's Compute-ACAM contribution (compiler, numerics)
  kernels/  — Pallas TPU kernels (interpreted on CPU, Mosaic on TPU)
  models/   — block-pattern transformer stack, digital + raceit exec modes
  configs/  — 10 assigned architectures + the paper's own models
  dist/     — sharding rules (DP/FSDP/TP/EP/SP), gradient compression
  train/    — AdamW, fault-tolerant loop
  serve/    — generation engine, request batching
  ckpt/     — atomic/async/elastic checkpointing
  data/     — checkpointable synthetic LM data
  hw/       — RACE-IT/PUMA/ReTransformer cycle+energy simulator
  launch/   — production meshes, multi-pod dry-run, HLO cost analyzer
"""
