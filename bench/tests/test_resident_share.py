"""``executor.resident_share`` on hand-made spans: the share of the
window's streamed ``exec.run`` calls whose output stayed on the device."""
import pytest

from bench import harness as H
from bench import program_spans as PS

MS = 1_000_000  # ns
THREAD = 1


def _run(start_ms, **args):
    return ("mfit.exec.run", start_ms * MS, 5 * MS, THREAD, args)


def _read(program):
    ps = PS.summarize([[("op", 0, MS)]], [("bench.window", 0, 100 * MS)],
                      program)
    real = PS.load
    PS.load = lambda ctx: ps
    try:
        return H.reader("executor.resident_share").read({})
    finally:
        PS.load = real


def test_share_of_streamed_calls_in_the_window():
    got = _read([_run(0, chunks=5, resident=1),     # a steady solve
                 _run(10, chunks=5, resident=1),    # its observe
                 _run(20, chunks=4, resident=0),    # over the budget
                 _run(30, chunks=1, resident=1),    # one chunk: not streamed
                 _run(120, chunks=5, resident=0)])  # after the window
    assert got == {"value": pytest.approx(200 / 3), "streamed_calls": 3,
                   "resident_calls": 2}


def test_nothing_to_read_without_streamed_calls():
    # a program whose exec.run carries no chunk count, as before the fit
    # rule, and one whose calls are all single-chunk
    assert _read([_run(0), _run(10)]) is None
    assert _read([_run(0, chunks=1, resident=1)]) is None
    assert _read([("mfit.rc.observe", 0, MS, THREAD, {})]) is None
