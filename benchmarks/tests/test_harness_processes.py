"""A run waits for the processes it started until they have really ended."""

import os
import subprocess
import sys
import time

from benchmarks import harness

# The first thread ends alone (the exit system call, not exit_group), so the
# process is a zombie whose second thread lives on for a while: what a killed
# worker looks like while the kernel takes its mappings apart.
THREADED_ZOMBIE = """
import ctypes, os, threading, time
threading.Thread(target=lambda: (time.sleep({linger}), os._exit(0))).start()
print("up", flush=True)
ctypes.CDLL(None).syscall({sys_exit}, 0)
"""


def test_a_zombie_whose_threads_still_run_has_not_ended():
    import platform

    sys_exit = {"x86_64": 60, "aarch64": 93}.get(platform.machine())
    if sys_exit is None or not sys.platform.startswith("linux"):
        import pytest

        pytest.skip("needs the number of the exit system call")
    child = subprocess.Popen(
        [sys.executable, "-c", THREADED_ZOMBIE.format(linger=1.5, sys_exit=sys_exit)],
        stdout=subprocess.PIPE,
    )
    assert child.stdout.readline().strip() == b"up"
    time.sleep(0.3)
    table = harness._proc_table()
    assert table[child.pid][1] == "Z"
    assert not harness._ended(child.pid, table)
    t = time.time()
    harness.wait_until_ended({child.pid})
    assert time.time() - t > 0.8
    assert child.pid not in harness._proc_table()  # reaped: all of it is gone


def test_another_process_child_is_judged_by_proc():
    """A grandchild cannot be reaped from here; /proc has to say it ended."""
    outer = subprocess.Popen(
        [sys.executable, "-c",
         "import subprocess, sys, time\n"
         "c = subprocess.Popen([sys.executable, '-c', 'import time; time.sleep(1.0)'])\n"
         "print(c.pid, flush=True)\ntime.sleep(3)"],
        stdout=subprocess.PIPE,
    )
    inner = int(outer.stdout.readline())
    assert not harness._ended(inner, harness._proc_table())
    t = time.time()
    harness.wait_until_ended({inner})  # ends as an unreaped zombie of `outer`
    assert 0.3 < time.time() - t < 2.5
    outer.kill()
    outer.wait()


def test_an_ended_process_is_not_waited_for():
    child = subprocess.Popen([sys.executable, "-c", "pass"])
    while child.pid in harness._proc_table() and harness._proc_table()[child.pid][1] != "Z":
        time.sleep(0.05)
    t = time.time()
    harness.wait_until_ended({child.pid})  # a zombie with no thread left
    assert time.time() - t < 1.0
    child.wait()


def _alive(pid: int) -> bool:
    table = harness._proc_table()
    return pid in table and table[pid][1] != "Z"


def test_a_process_that_lost_its_parent_is_found_by_the_mark_and_ended(monkeypatch):
    """``descendants`` cannot see a process whose parent has died; the mark
    in its environment can, and ``end_run`` kills it and waits for it."""
    monkeypatch.setenv(harness.MARK, "test-mark-1")
    outer = subprocess.Popen(
        [sys.executable, "-c",
         "import subprocess, sys\n"
         "c = subprocess.Popen([sys.executable, '-c', 'import time; time.sleep(60)'],\n"
         "                     start_new_session=True)\n"
         "print(c.pid, flush=True)"],
        stdout=subprocess.PIPE,
    )
    orphan = int(outer.stdout.readline())
    outer.wait()
    try:
        assert orphan not in harness.descendants()
        assert orphan in harness.marked() and orphan in harness.run_processes()
        monkeypatch.setenv(harness.MARK, "another-run")
        assert orphan not in harness.marked()
        monkeypatch.setenv(harness.MARK, "test-mark-1")
        t = time.time()
        harness.end_run()
        assert time.time() - t < 10.0
        assert not _alive(orphan)
    finally:
        if _alive(orphan):
            os.kill(orphan, 9)


def test_a_process_started_after_the_list_was_taken_is_killed_without_patience():
    first = subprocess.Popen([sys.executable, "-c", "import time; time.sleep(0.5)"])
    listed = harness.descendants()
    late = subprocess.Popen([sys.executable, "-c", "import time; time.sleep(60)"])
    t = time.time()
    harness.wait_until_ended(listed, patience_s=30.0, more=harness.descendants)
    assert time.time() - t < 10.0  # `first` ended by itself; `late` got no patience
    assert not _alive(first.pid) and not _alive(late.pid)


def test_a_run_that_is_told_to_stop_leaves_no_process(tmp_path):
    """SIGTERM in the middle of a serve cell's rehearsal: the replica, the
    proxy and the controller are ended before the run is, with no result.
    (The driver's check of PR 28 met a run whose deployment never came up:
    cut at its time limit, it left its workers behind.)"""
    import signal

    root = os.path.dirname(harness.HERE)
    cell = next(w["name"] for w in harness.benchmark()["workloads"]
                if harness.traffic_of(w)["kind"] != "train")
    run = subprocess.Popen(
        [sys.executable, os.path.join(harness.HERE, "run.py"), "--workload", cell, "--seed", "11", "--seconds", "60", "--trace", "0",
         "--cpu-rehearsal", "--out", str(tmp_path)],
        cwd=root, stdout=subprocess.PIPE, stderr=open(tmp_path / "err", "w"),
    )

    def children():
        return {p for p, row in harness._proc_table().items() if row[0] == run.pid}

    deadline = time.time() + 90
    while len(children()) < 2 and time.time() < deadline:
        time.sleep(0.2)
    started = children()
    assert len(started) >= 2
    run.send_signal(signal.SIGTERM)
    out, _ = run.communicate(timeout=60)
    assert run.returncode == 128 + signal.SIGTERM
    assert not any(line.startswith(b"{") for line in out.splitlines())
    assert not any(_alive(p) for p in started)
    # and the runtime was told not to take a worker's slow start for a dead node
    assert "a node is dead after 120 s of silence" in (tmp_path / "err").read_text()
