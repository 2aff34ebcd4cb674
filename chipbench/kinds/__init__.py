"""What the benchmark does with the answers of one kind of call. A
traffic mix names its kind (``"kind"``), and the harness loads
``kinds/<kind>.py`` by that name. A kind module gives:

- ``unsupported(spec)``: what of a call's specification its reference
  does not model, or None; the harness refuses such a cell at set-up;
- ``record(report)``: the numbers of one Report the metric readers see,
  with ``"kind"`` the estimator's name;
- ``check(config, calls)``: every number the comparison reads over the
  window's calls (``generator.Call`` and Report pairs), by name; the
  harness holds each to the configuration's ``limits``;
- ``control(config, call)``: the same numbers for the control, the
  plain reference one precision step down in the program's place.
"""

from __future__ import annotations

import importlib


def load(name: str):
    return importlib.import_module(f"chipbench.kinds.{name}")
