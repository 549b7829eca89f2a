"""Repo tooling namespace: stdlib-only CI gates that run before
dependency install (`tools.rtlint`, `tools/check_docs.py`), the
shared machinery both build on (`tools.pylib`), and a probe of the
host's dispatch cost that needs JAX (`tools/dispatch_probe.py`)."""
