"""Reader: peak device memory (`peak_bytes_in_use`, the fullest of the
cell's devices, read after the window), in GB.

spec: {"reader": "memory"}
"""


def read(spec: dict, ctx: dict):
    peak = ctx.get("memory_peak_bytes")
    return None if not peak else peak / 1e9
