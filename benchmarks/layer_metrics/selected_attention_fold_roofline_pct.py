"""The prefill attention kernel of a family with an indexer against its roofline: the least time the chip could take for the operations and bytes that attention over the selected rows alone needs in each prefill program of the traced window (the family's selected_attention at the tokens and the start its llm.prefill or llm.prefill_chunk span carries: a query at position i attends min(i + 1, index_topk) rows), averaged over those programs, over the device time a run of jit_paged_prefill spends in the operations named selected_attention_fold (one Mosaic call a stretch of the table a layer, ops/selected_attention.py). The kernel computes every position of every stretch under the selection's mask, so the share falls as contexts grow past index_topk: it says how far the dense form stands from the sparse ideal. None without a trace, peaks, such operations (a program built with the fold, a commit from before the kernel), runs of the program, or spans that carry tokens, or for a family that has no selected_attention."""

from benchmarks import flops_bytes, harness, stats

PREFILL, KERNEL = "jit_paged_prefill", "selected_attention_fold"


def read(records):
    if records["peaks"] is None:  # a CPU rehearsal has no peak to share
        return None
    trace = records["trace"]
    family = harness.family(records["config"])
    if trace is None or trace.get("t0_wall") is None or not hasattr(family, "selected_attention"):
        return None
    runs = sum(1 for name, _start, _dur in trace["program_runs"] if name.startswith(PREFILL))
    kernel_s = sum(s for name, s in trace["ops"] if name.startswith(KERNEL))
    t0 = trace["t0_wall"]
    fills = [
        s["extra"] for phase in ("llm.prefill", "llm.prefill_chunk")
        for s in stats.spans_in(records["spans"], phase, t0, t0 + trace["window_s"])
        if "tokens" in s["extra"]
    ]
    if not runs or not kernel_s or not fills:
        return None
    least = [
        flops_bytes.roofline_pct(
            *family.selected_attention(records["config"], x["tokens"], x.get("start", x.get("reused", 0))),
            1.0, records["peaks"],
        )[0] / 100.0
        for x in fills
    ]  # seconds
    return 100.0 * (sum(least) / len(least)) / (kernel_s / runs), "%"
