"""``memory_stats()["peak_bytes_reserved"]`` of the fullest chip, read after
the window: the region the TPU runtime keeps beside the allocator's arrays
for the loaded programs' temporaries (PERF.md section 4). Not part of
``memory_peak_bytes``; a backend that reports none gives nothing to read."""


def read(records, trace, cell):
    reserved = records.get("peak_reserved_bytes")
    return reserved / 1e9 if reserved else None
