"""peak_hbm_gib: the device allocator's peak_bytes_in_use after the
window, in GiB."""


def read(w):
    return None if w.run.peak_bytes is None else w.run.peak_bytes / 2 ** 30
