"""Plain reference implementations, one module per model family, found by
the ``reference`` name in a configuration file."""
