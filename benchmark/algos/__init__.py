"""One module per index kind, found by ``index.kind`` in a config file."""
