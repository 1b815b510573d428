"""Share of the traced window in which the device idled although the engine had work: 100 x (the window's idle seconds less those of the gaps labelled llm.idle) over (the window less the seconds of the llm.idle spans inside it). llm.idle is the engine's dry spell, from the return of the last step of a pump that ran dry to the start of the next pump (ray_tpu/llm/serve_llm.py), and reduce_trace names an idle gap after the shortest span over its middle, so a gap under it is the device waiting for a request and not for a late host. Equals device_idle_pct.code where the engine never runs dry (no such span: a cell above the knee, or a commit from before the span, which reads the same number under this name). None without a trace on the wall clock."""

IDLE = "llm.idle"


def read(records):
    trace = records["trace"]
    if trace is None or trace.get("t0_wall") is None:
        return None
    t0, t1 = trace["t0_wall"], trace["t0_wall"] + trace["window_s"]
    dry_s = sum(
        max(0.0, min(t1, s["t"] + s["dur_s"]) - max(t0, s["t"]))
        for s in records["spans"] if s["phase"] == IDLE
    )
    idle_s = trace["window_s"] - trace["busy_s"]
    idle_dry_s = sum(seconds for label, seconds in trace["idle_gaps"] if label == IDLE)
    if trace["window_s"] - dry_s <= 0:
        return None
    return 100.0 * (idle_s - idle_dry_s) / (trace["window_s"] - dry_s), "%"
