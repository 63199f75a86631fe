"""The benchmark of mlsl_tpu: one process, one cell, one run (see README.md)."""
