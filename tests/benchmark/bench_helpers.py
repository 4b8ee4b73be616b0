"""Shared helpers of the benchmark's tests."""

import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def small_config(rm_count=3, batch=8, **over):
    """A 2pc configuration at a CPU size, in the configuration files'
    shape."""
    cfg = {"name": f"2pc-{rm_count}", "model": "twopc",
           "params": {"rm_count": rm_count},
           "spawn": {"batch_size": batch, "table_capacity": 1 << 16,
                     "arena_capacity": 1 << 16},
           "row_bits": 4 * rm_count + 4,
           "reference": {"module": "twopc",
                         "params": {"rm_count": rm_count}}}
    cfg.update(over)
    return cfg
