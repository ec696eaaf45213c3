"""Peak device memory of the fullest chip after the window
(``memory_stats()["peak_bytes_in_use"]``), in GiB."""


def read(ctx):
    return ctx["memory_peak_bytes"] / 2 ** 30
