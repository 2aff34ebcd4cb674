"""The item-size reference against the program at small sizes on the
CPU: its draw equals the program's ``item`` lengths, and the
``monte_carlo_items`` comparison reads a sound Report as equal and one
byte of one item's size as a difference."""

from __future__ import annotations

import numpy as np
import pytest

from chipbench import generator, reference_items
from chipbench import reference_mc as ref_mc
from chipbench.conftest import SMALL
from chipbench.kinds import monte_carlo_items

MIX = generator.load("traffic", "mc_items")
SEED = 2_718_281_828_459


def small_call(seed: int):
    config = generator.merged(generator.load("configs", "etc_j9"), SMALL["etc_j9"])
    return generator.Traffic(config, MIX, seed).next_call()


@pytest.mark.parametrize("seed", [0, 7, SEED, 2**63 - 25])
@pytest.mark.parametrize("max_len", [2**20, 900])
def test_reference_draw_equals_the_programs_items(seed, max_len):
    from repro.scenario import LengthSpec

    spec = {**generator.load("configs", "etc_j9")["scenario"]["workload"]["lengths"],
            "max_len": max_len}
    want = LengthSpec(**{k: tuple(v) if isinstance(v, list) else v for k, v in spec.items()})
    for n in (1, 5_000):
        got = reference_items.item_lengths(spec, n, seed)
        assert np.array_equal(got, want.materialize(n, seed))


def test_defaults_are_the_programs():
    from repro.scenario import LengthSpec

    spec = LengthSpec(kind="item", max_len=2**20)
    assert reference_items.ETC_KEY == spec.key_gev
    assert reference_items.ETC_VALUE == spec.value_gpd


@pytest.fixture(scope="module")
def sound():
    call = small_call(SEED)
    return call, call.scenario.run()


def test_sound_report_reads_no_mismatch(sound):
    call, report = sound
    assert report.backend == "xla"
    assert monte_carlo_items.unsupported(call.spec) is None
    got = monte_carlo_items.compare(call.spec, report)
    assert got["mismatches"] == 0, got["failed_fields"]
    assert got["rate_gap"] <= 1e-12
    # the sizes reach the engine: sets that evict several items
    assert sum(c for k, c in report.ripple["evictions_per_set"].items() if int(k) >= 2)


def test_one_byte_off_in_one_item_reads_mismatches(sound, monkeypatch):
    call, report = sound
    draw = reference_items.item_lengths

    def one_byte_more(spec, n_objects, length_seed):
        sizes = draw(spec, n_objects, length_seed)
        sizes[0] += 1  # the most popular item of every proxy
        return sizes

    monkeypatch.setattr(reference_items, "item_lengths", one_byte_more)
    got = monte_carlo_items.compare(call.spec, report)
    assert got["mismatches"] > 0 and "final_vlen" in got["failed_fields"]


def test_other_lengths_are_refused():
    spec = generator.merged(small_call(3).spec, {"workload": {"lengths": {"kind": "unit"}}})
    assert monte_carlo_items.unsupported(spec)
    assert ref_mc.unsupported(spec) is None
