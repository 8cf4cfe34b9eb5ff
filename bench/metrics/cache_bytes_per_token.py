"""Bytes of storage-form activations (b0, every tap, b_final, with their
int8 scales) the cache holds, and the prefetcher hands the cached step,
per training token. A count."""


def read(record):
    if "cache_bytes" not in record:
        return None
    return record["cache_bytes"] / record["cache_tokens"]
