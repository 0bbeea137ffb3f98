"""Pallas TPU kernel layer (optional substrate).

Compute hot-spots the paper's workloads motivate get a custom kernel
here — attention (flash/decode), linear scans (Mamba/RWKV), matmul
with fused epilogues, bilinear resize, and the pre/post-processing set
(``preproc``: YUV decode, fused letterbox+normalize, pairwise IoU).
Every op is reachable through :mod:`repro.kernels.ops`, which
dispatches between ``ref`` (pure-jnp oracle), ``xla`` (memory-bounded
JAX, lowers anywhere), and ``pallas`` (TPU kernels, compiled by
Mosaic; tests alone run them with ``interpret=True`` on the CPU);
tilings resolve through the persistent autotune cache
(:mod:`repro.kernels.autotune`).
"""
