"""Parallelism: the seq engine sharded over a jax.sharding.Mesh.

The reference scales by Kafka partition rebalancing (SURVEY.md §2.3);
here the symbol axis is sharded over a jax.sharding.Mesh
(parallel/seqmesh.py), account state is replicated with exact psum
delta-merges, and collectives ride ICI.
"""
