"""Peak device memory of the fullest chip, GiB, as the backend reports it
(``memory_stats()["peak_bytes_in_use"]``), read before the reference runs."""


def read(ctx: dict):
    return ctx["memory_peak_bytes"] / 2.0 ** 30
