"""The on-chip benchmark of the coded-MPC path (see ``BENCHMARK.json``)."""
