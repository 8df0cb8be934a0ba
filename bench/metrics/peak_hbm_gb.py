"""Peak device memory in use (GB, 1e9 bytes) on the fullest chip, read
after the window and before the reference runs."""


def read(r):
    return None if r.peak_bytes is None else r.peak_bytes / 1e9
