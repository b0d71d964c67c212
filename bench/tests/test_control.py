"""Each cell's control, the plain reference in the next lower precision
put in the program's place, fails a limit that the program passes, at a
size a test holds and with the limits the configurations state."""
from __future__ import annotations

import pytest

from conftest import run


@pytest.mark.parametrize("cell", ["cg.hpcg104", "serve.granite.decode"])
def test_control_fails_where_the_program_passes(checkout, cell):
    out = run(checkout, cell, seconds=2.0, control=True)
    assert out.result["correct"], out.result["checks"]
    limits = {c["name"]: c["limit"] for c in out.checks}
    failed = [c["name"] for c in out.control
              if c["value"] > limits[c["name"]]]
    assert failed, (out.control, limits)
