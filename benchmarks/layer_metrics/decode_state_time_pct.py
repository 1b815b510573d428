"""The share of the decode program's device time spent inside the state step kernel: 100 x the seconds of the operations whose name begins state_step_ssd over the seconds of the runs of jit_paged_decode, both over the traced window. Lower is better, as for the other shares of device time: a faster state step lowers it. Its size says whether the mechanism the cell was chosen for (a fixed float32 state a session, stepped once a token) does the work there. None without a trace, runs of the program or such operations (a program built with the plain step has none)."""

from benchmarks import kind_kernel

STATE_STEP = "state_step_ssd"


def read(records):
    found = kind_kernel.traced_steps(records)
    if found is None:
        return None
    trace, _steps = found
    runs, kernel_s = kind_kernel.decode_runs(trace), kind_kernel.op_seconds(trace, STATE_STEP)
    if not runs or not kernel_s:
        return None
    return 100.0 * kernel_s / sum(runs), "%"
