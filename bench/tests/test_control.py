"""The correctness control: the program's own int4 page path, the next
precision below the configuration's int8, must fail the comparison the
int8 program passes (here at a size a test run holds; on the chip at
the cell's own size, PERF.md section 2)."""
from bench.tests import small


def test_int4_pages_fail_where_int8_passes(monkeypatch):
    good = small.run(monkeypatch, seed=21)
    ctrl = small.run(monkeypatch, kv_dtype="int4", seed=21)
    g = good["checks"]["widest_gap"]["value"]
    c = ctrl["checks"]["widest_gap"]["value"]
    assert good["correct"] is True
    assert ctrl["correct"] is False
    assert c >= 3 * g
