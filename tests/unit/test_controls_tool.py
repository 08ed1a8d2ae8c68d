"""``tools/controls.py``: one frame, a table of faults a cell. Each cell's
table is tried on its toy (``--tiny``, the CPU): the sound program is not
told from the reference, one fault of the table is. Never a reading: what the
comparison tells at the published widths is the chip's (PERF.md)."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# (the cell, a fault of its table that a toy's eight tokens already show)
CASES = [
    ("mimo-v2-flash.serve-agent-long-closed64", "no_sink"),
    ("longcat-flash-chat.serve-tool-agent-closed64", "plane_swapped"),
    ("jamba2-3b.serve-doc-reason-closed64", "rotary"),
    ("kimi-linear-48b-a3b.serve-doc-xlong-closed64", "rotary"),
]


@pytest.mark.parametrize("cell,fault", CASES, ids=[c.split(".serve")[0] for c, _ in CASES])
def test_a_cells_table_tells_a_fault_from_the_sound_program(cell, fault):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)  # the tool asks for its own devices
    run = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "controls.py"), "--cell", cell, "--tiny",
         "--seeds", "1", "--requests", "1", "--cap", "8", "--controls", f"sound,{fault}"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert run.returncode == 0, run.stderr[-2000:]
    lines = [json.loads(ln[len("CONTROL "):]) for ln in run.stdout.splitlines()
             if ln.startswith("CONTROL ")]
    told = {ln["control"]: ln["told"] for ln in lines}
    assert told["sound"] is False and told[fault] is True, lines
    for ln in lines:
        assert ln["limit"] > 0 and ln["seed"] == 1 and len(ln["shortfall"]) == 1
        if "lens" in ln:  # a served control: one request of at most 8 tokens
            assert len(ln["lens"]) == 1 and 0 < ln["lens"][0][1] <= 8


def test_an_unknown_control_or_cell_is_refused():
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import controls
    finally:
        sys.path.pop(0)
    assert sorted(controls.CELLS) == sorted(c for c, _ in CASES)
    with pytest.raises(SystemExit):
        controls.main(["--cell", "qwen3-1.7b.serve-decode-closed64", "--seeds", "1"])
