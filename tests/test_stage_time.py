"""``benchmarks/stage_time.py``: the arithmetic of device time by stage, on a
small hand-made trace; and the scope path's way out of an ``.xplane.pb``.
"""

import json

import pytest

from benchmarks import stage_time

US = 1_000_000  # the trace's clock is in picoseconds

PREFILL = "jit_paged_prefill(11)"
DECODE = "jit_paged_decode(7)"


def op(name, start_us, dur_us, path=""):
    return [name, start_us * US, dur_us * US, stage_time.stage_of(path)]


def hand_made():
    """One device. A prefill run of 100 us and two decode runs of 50 us.
    ``fusion.1`` is the prefill's dense product and the decode program's
    state step: one name, two instructions, two stages. The prefill's
    ``while.2`` holds two body operations and 4 us of its own between them."""
    return {"planes": [{
        "name": "/device:TPU:0",
        "modules": [[PREFILL, 0, 100 * US], [DECODE, 200 * US, 50 * US], [DECODE, 300 * US, 50 * US]],
        "ops": [
            op("fusion.1", 0, 30, "jit(paged_prefill)/jit(main)/st.mlp/dot_general"),
            op("while.2", 30, 50, "jit(paged_prefill)/jit(main)/while"),
            op("fusion.3", 32, 20, "jit(paged_prefill)/jit(main)/while/body/st.state_in/dot_general"),
            op("paged_prefill_attention.4", 54, 24, "jit(paged_prefill)/jit(main)/while/body/st.attn_core/pallas_call"),
            op("copy.5", 80, 10, "pool['ckv']"),
            # 90-100: the run goes on and no operation runs
            op("fusion.1", 200, 40, "jit(paged_decode)/jit(main)/st.state_scan/mul"),
            op("fusion.6", 240, 10, "jit(paged_decode)/jit(main)/transpose(jvp(st.mlp))/dot_general"),
            op("fusion.1", 300, 40, "jit(paged_decode)/jit(main)/st.state_scan/mul"),
            op("fusion.6", 340, 5, "jit(paged_decode)/jit(main)/transpose(jvp(st.mlp))/dot_general"),
            op("fusion.9", 400, 5, "jit(other)/st.mlp/add"),  # inside no run of a program
        ],
    }]}


def test_a_program_at_a_time_and_an_operation_at_a_time():
    programs = stage_time.stage_times(hand_made())
    assert set(programs) == {PREFILL, DECODE}
    pre, dec = programs[PREFILL], programs[DECODE]
    assert (pre["runs"], dec["runs"]) == (1, 2)
    assert pre["total_s"] == pytest.approx(100e-6) and dec["total_s"] == pytest.approx(100e-6)
    # the while counts its own 6 us (30-32, 52-54, 78-80) and not its body twice
    assert pre["stages"] == pytest.approx({"attn_core": 24e-6, "mlp": 30e-6, "state_in": 20e-6})
    assert pre["unnamed_top"] == [["copy.5", pytest.approx(10e-6)], ["while.2", pytest.approx(6e-6)]]
    assert pre["between_ops_s"] == pytest.approx(10e-6)
    assert pre["unnamed_s"] == pytest.approx(26e-6)
    assert pre["longest"][:2] == [["fusion.1", "mlp", pytest.approx(30e-6)],
                                  ["paged_prefill_attention.4", "attn_core", pytest.approx(24e-6)]]
    assert ["copy.5", None, pytest.approx(10e-6)] in pre["longest"]
    # the same name under another stage in another program
    assert dec["stages"] == pytest.approx({"mlp": 15e-6, "state_scan": 80e-6})
    assert dec["unnamed_top"] == [] and dec["unnamed_s"] == pytest.approx(5e-6)
    for p in (pre, dec):  # shares and unnamed add up to the runs' time
        assert sum(p["stages"].values()) + p["unnamed_s"] == pytest.approx(p["total_s"])


def test_seconds_are_averaged_over_the_devices():
    one = hand_made()["planes"][0]
    other = {**one, "name": "/device:TPU:1", "ops": [o for o in one["ops"] if o[0] != "copy.5"]}
    pre = stage_time.stage_times({"planes": [one, other]})[PREFILL]
    assert pre["runs"] == 1 and pre["total_s"] == pytest.approx(100e-6)
    assert pre["stages"]["mlp"] == pytest.approx(30e-6)
    assert pre["unnamed_top"][0] == ["while.2", pytest.approx(6e-6)]
    assert pre["unnamed_top"][1] == ["copy.5", pytest.approx(5e-6)]
    assert pre["between_ops_s"] == pytest.approx(15e-6)


def test_the_programs_are_found_by_name_and_a_train_step_by_its_time():
    kinds = stage_time.by_kind(stage_time.stage_times(hand_made()))
    assert set(kinds) == {"prefill", "decode"}
    assert kinds["prefill"]["programs"] == [PREFILL] and kinds["decode"]["runs"] == 2
    train = {"planes": [{
        "name": "/device:TPU:0",
        "modules": [["jit_step_fn(3)", 0, 90 * US], ["jit_convert_element_type(4)", 95 * US, 1 * US]],
        "ops": [
            op("flash_fwd.1", 0, 30, "jit(step_fn)/jit(main)/st.attn_core/pallas_call"),
            op("fusion.2", 30, 60, "jit(step_fn)/jit(main)/st.optimizer/mul"),
            op("convert.1", 95, 1),
        ],
    }]}
    kinds = stage_time.by_kind(stage_time.stage_times(train))
    assert set(kinds) == {"train"} and kinds["train"]["programs"] == ["jit_step_fn(3)"]
    assert kinds["train"]["stages"] == pytest.approx({"attn_core": 30e-6, "optimizer": 60e-6})


def test_two_buckets_of_prefill_are_one_kind_and_their_operations_keep_their_programs():
    plain = hand_made()
    plane = plain["planes"][0]
    other = "jit_paged_prefill(12)"
    plane["modules"].append([other, 500 * US, 40 * US])
    plane["ops"] += [
        op("fusion.1", 500, 30, "jit(paged_prefill)/jit(main)/st.attn_proj/dot_general"),
        op("copy.5", 530, 10, ""),
    ]
    merged = stage_time.by_kind(stage_time.stage_times(plain))["prefill"]
    assert merged["runs"] == 2 and merged["total_s"] == pytest.approx(140e-6)
    assert merged["stages"]["attn_proj"] == pytest.approx(30e-6) and merged["stages"]["mlp"] == pytest.approx(30e-6)
    assert [name for name, _s in merged["unnamed_top"]] == [
        f"copy.5 of {PREFILL}", f"copy.5 of {other}", f"while.2 of {PREFILL}"]
    assert sum(merged["stages"].values()) + merged["unnamed_s"] == pytest.approx(merged["total_s"])


@pytest.mark.parametrize("path,stage", [
    ("jit(paged_prefill)/jit(main)/while/body/st.state_in/dot_general", "state_in"),
    ("jit(step_fn)/jit(main)/transpose(jvp(st.mlp))/dot_general", "mlp"),
    ("jit(step_fn)/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/st.attn_proj/mul", "attn_proj"),
    ("jit(f)/st.attn_core/min;jit(f)/st.attn_core/sub", "attn_core"),  # two the compiler merged
    ("jit(f)/st.router/jit(argsort)/sort", "router"),
    ("jit(paged_decode)/jit(main)/while/body/dynamic_slice", None),
    ("params['layers'][4]['e_up']", None),
    ("jit(f)/first.step/add", None),  # a word that ends in "st." is no stage
    ("", None),
])
def test_the_stage_a_scope_path_names(path, stage):
    assert stage_time.stage_of(path) == stage


def varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(number: int, value) -> bytes:
    """One protobuf field in wire format: an int as a varint, bytes or str
    length-delimited."""
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    value = value.encode() if isinstance(value, str) else value
    return varint(number << 3 | 2) + varint(len(value)) + value


def xplane(name, stat_names, instructions, lines) -> bytes:
    """An ``XPlane``: ``instructions`` {metadata id: (HLO line, {stat id:
    str or ("ref", stat id)})}, ``lines`` [(name, timestamp_ns, [(metadata
    id, offset_ps, duration_ps)])]."""
    out = field(2, name)
    for key, text in stat_names.items():
        out += field(5, field(1, key) + field(2, field(1, key) + field(2, text)))
    for key, (long_name, stats) in instructions.items():
        meta = field(1, key) + field(2, long_name)
        for stat_id, value in stats.items():
            said = field(7, value[1]) if isinstance(value, tuple) else field(5, value)
            meta += field(5, field(1, stat_id) + said)
        out += field(4, field(1, key) + field(2, meta))
    for line_name, t0_ns, events in lines:
        body = field(2, line_name) + field(3, t0_ns)
        for meta_id, offset, dur in events:
            # the event's own statistics (field 4) are not the metadata's
            body += field(4, field(1, meta_id) + field(2, offset) + field(3, dur) + field(4, field(1, 9) + field(3, 7)))
        out += field(3, body)
    return out


def test_the_scope_path_is_read_off_the_events_metadata_in_the_files_wire_format(tmp_path):
    """Two programs hold a ``fusion.1``: two records of metadata, each with
    its own ``tf_op``, and every event finds its own by id. A string kept
    once is a reference to a stat's metadata; a host plane is passed over."""
    stat_names = {3: "program_id", 26: "tf_op", 40: "jit(paged_decode)/jit(main)/st.state_scan/mul:"}
    instructions = {
        1: ("%fusion.1 = bf16[8,128]{1,0} fusion(bf16[8,128]{1,0} %p), kind=kLoop", {26: "jit(paged_prefill)/jit(main)/st.mlp/dot_general:"}),
        2: ("%fusion.1 = bf16[8,128]{1,0} fusion(bf16[8,128]{1,0} %p), kind=kLoop", {26: ("ref", 40)}),
        3: ("%copy.5 = bf16[8,128]{0,1} copy(bf16[8,128]{1,0} %q)", {}),
        4: (PREFILL, {}),
        5: (DECODE, {}),
    }
    device = xplane("/device:TPU:0", stat_names, instructions, [
        ("XLA Modules", 2, [(4, 0, 100 * US), (5, 200 * US, 50 * US)]),
        ("XLA Ops", 1, [(1, 1000, 60 * US), (3, 1000 + 60 * US, 30 * US), (2, 1000 + 200 * US, 40 * US)]),
        ("Async XLA Ops", 1, [(3, 0, 5 * US)]),
    ])
    host = xplane("/host:CPU", {}, {1: ("thread", {})}, [("python", 0, [(1, 0, 9)])])
    path = tmp_path / "vm.xplane.pb"
    path.write_bytes(field(1, host) + field(1, device) + field(3, "a field of XSpace that is no plane"))
    plain = stage_time.plain_from_xplane(str(path))
    (plane,) = plain["planes"]
    assert plane["name"] == "/device:TPU:0"
    assert plane["modules"] == [[PREFILL, 2000, 100 * US], [DECODE, 2000 + 200 * US, 50 * US]]
    assert plane["ops"] == [
        ["fusion.1", 2000, 60 * US, "mlp"],
        ["copy.5", 2000 + 60 * US, 30 * US, None],
        ["fusion.1", 2000 + 200 * US, 40 * US, "state_scan"],
    ]
    programs = stage_time.stage_times(plain)
    assert programs[PREFILL]["stages"] == pytest.approx({"mlp": 60e-6})
    assert programs[PREFILL]["unnamed_top"] == [["copy.5", pytest.approx(30e-6)]]
    assert programs[DECODE]["stages"] == pytest.approx({"state_scan": 40e-6})


def records_of(tmp_path, monkeypatch, kinds):
    """``records`` as a traced run's, with the pass over the trace stood in for."""
    monkeypatch.setenv("RAY_TPU_FLIGHTREC_DUMP_DIR", str(tmp_path / "flightrec_dumps"))
    xplane = tmp_path / "trace" / "plugins" / "profile" / "now" / "vm.xplane.pb"
    xplane.parent.mkdir(parents=True)
    xplane.write_bytes(b"")
    monkeypatch.setattr(stage_time, "plain_from_xplane", lambda path: kinds)
    return {"trace": {"window_s": 1.0}}


def test_a_reader_reads_a_share_and_every_reader_of_a_run_shares_one_pass(tmp_path, monkeypatch, capsys):
    records = records_of(tmp_path, monkeypatch, hand_made())
    assert stage_time.share(records, "prefill", "mlp") == (pytest.approx(30.0), "%")
    assert stage_time.share(records, "prefill", None) == (pytest.approx(26.0), "%")
    assert stage_time.share(records, "decode", "state_scan") == (pytest.approx(80.0), "%")
    assert stage_time.share(records, "decode", "router") == (0.0, "%")  # has stages, spent nothing there
    assert stage_time.share(records, "train", "mlp") is None  # no such program in the trace
    shares = [stage_time.share(records, "prefill", s)[0] for s in ("attn_core", "mlp", "state_in")]
    assert sum(shares) + stage_time.share(records, "prefill", None)[0] == pytest.approx(100.0)
    written = json.loads((tmp_path / "stage_times.json").read_text())
    assert written["kinds"]["prefill"]["stages"]["mlp"] == pytest.approx(30e-6)
    assert set(written["programs"]) == {PREFILL, DECODE} and written["pass_s"] >= 0
    notes = [line for line in capsys.readouterr().out.splitlines() if line.startswith("note stage times")]
    assert len(notes) == 3  # the pass, and a line a kind of program: once, whatever the readers


def test_without_a_trace_or_without_a_staged_operation_a_reader_reads_nothing(tmp_path, monkeypatch):
    assert stage_time.share({"trace": None}, "prefill", "mlp") is None
    bare = hand_made()
    for o in bare["planes"][0]["ops"]:
        o[3] = None  # a commit from before the stages: every operation unnamed
    records = records_of(tmp_path, monkeypatch, bare)
    assert stage_time.share(records, "prefill", "mlp") is None
    assert stage_time.share(records, "prefill", None) is None
