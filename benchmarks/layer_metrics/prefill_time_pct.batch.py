"""Share of the engine's device time that goes to prefill in a cell above the knee: device seconds of the prefill programs over those of the prefill and decode programs, in the traced window. Programs are told apart by the names the engine gives them (jit_paged_prefill / jit_paged_decode, PR 26), not by the host span that precedes them: where a launch follows its span's start by 0.1-0.3 ms, as in the cell this reads, the clock anchor's drift over a traced window is enough to pair a span with the next program (PERF.md section 7, PR 29)."""

PREFILL, DECODE = "jit_paged_prefill", "jit_paged_decode"


def read(records):
    trace = records["trace"]
    if trace is None:
        return None
    seconds = {PREFILL: 0.0, DECODE: 0.0}
    for name, _start, dur_ns in trace["program_runs"]:
        for program in seconds:
            if name.startswith(program):
                seconds[program] += dur_ns / 1e9
    if sum(seconds.values()) == 0:
        return None
    return 100.0 * seconds[PREFILL] / sum(seconds.values()), "%"
