"""General harness code: every cell is driven by data under ``bench/``."""
