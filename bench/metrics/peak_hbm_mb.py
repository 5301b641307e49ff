"""Peak device memory in MB (1e6 bytes): ``peak_bytes_in_use`` of the
fullest chip the cell uses, read from the device after the window."""


def read(run):
    peak = run.get("memory_peak_bytes")
    return None if peak is None else peak / 1e6
