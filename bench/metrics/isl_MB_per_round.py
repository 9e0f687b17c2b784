"""isl_MB_per_round: bytes one round puts on the ISL, all nodes together:
over the collective-permutes of the cell's compiled round program, operand
bytes x source-target pairs (``bench/hlo.py``), in MB (1e6 bytes)."""


def read(ctx):
    b = ctx.get("isl_bytes_per_round")
    return b / 1e6 if b else None
