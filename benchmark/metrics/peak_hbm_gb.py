"""``memory_stats()["peak_bytes_in_use"]`` of the fullest chip, read after
the window and before the reference runs: the arrays the process held (data,
pool, optimizer stacks). On a TPU it leaves out the programs' temporaries;
``peak_hbm_reserved_gb`` reads those."""


def read(records, trace, cell):
    return records["peak_bytes"] / 1e9 if records.get("peak_bytes") else None
