"""Peak device memory on the fullest chip, read after the window."""


def read(params: dict, context: dict):
    peak = context.get("memory_peak_bytes")
    return None if peak is None else peak / 2 ** 30
