"""Reproduction of "Communication-Aware Diffusion Load Balancing for
Persistently Interacting Objects" grown toward a production-scale JAX
system.
"""
