"""The committed four-chip cell, ``c10-t3-wire-4chip``, loads from its
own files: cell 1's configuration and traffic with the users over a
(4, 1) mesh, the three numbers of ``correct`` with a limit each, and
the per-layer metrics of cell 1.  Runs of the cell on four virtual CPU
devices are ``test_correct.py::test_four_device_cell``'s."""
import harness

CELL = "c10-t3-wire-4chip"


def test_committed_four_chip_cell():
    cell, cfg, traffic, limits, spec = harness.load_cell(CELL)
    one, cfg1, traffic1, limits1, _ = harness.load_cell("c10-t3-wire")
    assert cell["chips"] == 4 and cell["config"] == one["config"]
    assert cfg == cfg1
    assert traffic["mesh"] == [4, 1]
    assert {k: v for k, v in traffic.items() if k not in ("mesh", "note")} \
        == {k: v for k, v in traffic1.items() if k not in ("mesh", "note")}
    assert sorted(limits) == sorted(limits1) \
        == ["flips_rest", "latency_gap", "update_diff"]
    assert all(0 < v for v in limits.values())
    names = [m["name"] for m in harness.per_layer_metrics(spec, CELL)]
    assert names == [m["name"] for m in
                     harness.per_layer_metrics(spec, "c10-t3-wire")]
    assert [m["name"] for m in harness.end_to_end_metrics(spec, CELL)] \
        == ["round_s", "round_p95_s", "setup_s"]
