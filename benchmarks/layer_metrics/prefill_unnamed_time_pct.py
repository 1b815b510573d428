"""The share of the device time of the prefill programs (jit_paged_prefill*) that no stage of the program's vocabulary names: operations whose scope path holds no st.<stage> (the compiler's own copies and re-layouts, a scan's slicing of its stacked weights) and the time inside a run in which no operation ran. 100 x that over the seconds of the program's runs in the traced window (benchmarks/stage_time.py; its note line names the five longest such operations). Lower is better: what it holds cannot be read by stage. None without a trace, runs of the program or any staged operation (a commit from before the stages)."""

from benchmarks import stage_time


def read(records):
    return stage_time.share(records, "prefill", None)
