"""Peak device bytes after the window, on the fullest chip, in MB."""


def read(ctx: dict, selector: dict):
    peak = ctx["memory_peak_bytes"]
    return peak / 1e6 if peak else None
