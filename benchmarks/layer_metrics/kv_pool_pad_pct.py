"""Bytes of the block pool as the device lays it out over the bytes the mathematics needs for the same blocks, less one: 100 x (sum of the engine's cache_bytes_laid_* counters / sum of its cache_bytes_needed_* counters - 1), both written once at the engine's construction (the second from the family's record of what a position costs in all layers of a table kind, times the kind's blocks). Keys of 192 lanes stored in rows of 256 beside values of 128 read 20; 0 once keys lie unpadded. None where the engine writes no such counter, as for a family whose record prices no position and on a commit from before the counters."""


def read(records):
    stats = records.get("engine_stats") or {}
    laid = sum(v for k, v in stats.items() if k.startswith("cache_bytes_laid_"))
    needed = sum(v for k, v in stats.items() if k.startswith("cache_bytes_needed_"))
    if not laid or not needed:
        return None
    return 100.0 * (laid / needed - 1.0), "%"
