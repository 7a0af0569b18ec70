"""Peak device memory on the fullest chip, in GB (1e9 bytes), read at
the end of the window: the allocator's ``peak_bytes_in_use`` (arrays)
plus its ``peak_bytes_reserved`` (a running program's scratch), as
``harness.memory_peak_bytes`` explains. The two peaks need not fall at
the same moment, so this is an upper bound of what the chip held, and
the same figure as the result line's ``memory_peak_bytes``. It bounds
the batch x sequence a chip takes."""


def read(ctx):
    if ctx.memory_peak_bytes is None:
        return None
    return ctx.memory_peak_bytes / 1e9
