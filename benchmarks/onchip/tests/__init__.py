"""Tests of the on-chip benchmark (a package, so that its ``conftest``
does not shadow the one of the repository's ``tests/``)."""
