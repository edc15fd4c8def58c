"""Reproduction of "Performance of Small Language Model Pretraining on
FABRIC: An Empirical Study" grown toward a production-scale jax system.

Importing ``repro`` imports no jax, so a launcher can still choose its
platform and device count (``repro.launch.simulate_host_devices``) after
``import repro``.
"""
