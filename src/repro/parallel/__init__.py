"""JAX spellings the engine's ``shard_map`` path needs (see ``compat``)."""
