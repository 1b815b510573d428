"""The share of the decode program's device time spent inside the decode attention kernel: 100 x the seconds of the operations whose name begins paged_decode_attention (every layer kind's calls) over the seconds of the runs of jit_paged_decode, both over the traced window. Lower is better, as for the other shares of device time: a faster kernel lowers it. Its size says whether the mechanism a cell was chosen for does the work there: a cell whose decode is its experts' weights reads a few percent. None without a trace, runs of the program or such operations (a program built with the gather has none)."""

from benchmarks import kind_kernel


def read(records):
    found = kind_kernel.traced_steps(records)
    if found is None:
        return None
    trace, _steps = found
    runs, kernel_s = kind_kernel.decode_runs(trace), kind_kernel.op_seconds(trace, kind_kernel.KERNEL)
    if not runs or not kernel_s:
        return None
    return 100.0 * kernel_s / sum(runs), "%"
