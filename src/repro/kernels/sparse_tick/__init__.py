"""sparse_tick kernel package: the sparse event tick's arbiter policies."""
