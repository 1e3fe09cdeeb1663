"""Mesh data plane below the coprocessor: pure-jnp row routing
(exchange.py). Imports nothing from tidb_tpu.copr; placements that use
it live in copr/placement.py."""
