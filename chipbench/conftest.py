"""Sizes at which the CPU tests run the cells that ``test_faults.py``
does not size itself: each is laid over its configuration as that
file's ``SMALL`` entries are."""

import pytest

SMALL = {
    # the Fig-2 store at 3,000 items; budgets ~30% of the catalogue's
    # bytes in the 1:2:7 classes, so lists hold tens of items
    "etc_j9": {
        "scenario": {
            "workload": {"n_objects": 3000},
            "system": {
                "allocations": [11400] * 3 + [22800] * 3 + [79800] * 3,
                "physical_capacity": 342000,
            },
            "n_requests": 6000,
            "warmup": 600,
        },
    },
}


@pytest.fixture(autouse=True)
def _small_sizes(request, monkeypatch):
    sized = getattr(request.module, "SMALL", None)
    if isinstance(sized, dict) and sized is not SMALL:
        for name, over in SMALL.items():
            if name not in sized:
                monkeypatch.setitem(sized, name, over)
