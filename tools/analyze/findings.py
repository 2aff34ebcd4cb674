"""Finding and waiver plumbing shared by every ``tools.analyze`` rule.

A :class:`Finding` is one violation of a repo invariant, reported with a
stable ``(rule, code, path)`` identity so ``waivers.toml`` entries keep
matching across unrelated line drift. Waivers are the single suppression
mechanism — there are no inline ``# noqa``-style pragmas — so every
intentional exception lives in one reviewed file with a reason string.
"""

from __future__ import annotations

import tomllib
from dataclasses import dataclass, field
from pathlib import Path
from typing import List, Optional


@dataclass
class Finding:
    """One rule violation at a source location."""

    rule: str      # rule name, e.g. "determinism"
    code: str      # stable finding code within the rule, e.g. "wall-clock"
    path: str      # repo-relative posix path
    line: int      # 1-based line number (0 = whole-file finding)
    message: str
    waived: bool = False
    waiver_reason: str = ""

    def to_dict(self) -> dict:
        return {
            "rule": self.rule,
            "code": self.code,
            "path": self.path,
            "line": self.line,
            "message": self.message,
            "waived": self.waived,
            "waiver_reason": self.waiver_reason,
        }

    def render(self) -> str:
        loc = f"{self.path}:{self.line}" if self.line else self.path
        text = f"{loc}: [{self.rule}/{self.code}] {self.message}"
        if self.waived:
            text += f"  (waived: {self.waiver_reason})"
        return text


@dataclass
class Waiver:
    """One entry of ``waivers.toml``.

    Matches a finding when ``rule`` and ``path`` are equal, ``code``
    (when given) is equal, and ``contains`` (when given) is a substring
    of the finding message. ``reason`` is mandatory — a waiver without a
    why is a suppression, not an exception.
    """

    rule: str
    path: str
    reason: str
    code: Optional[str] = None
    contains: Optional[str] = None
    used: int = field(default=0, compare=False)

    def matches(self, f: Finding) -> bool:
        if self.rule != f.rule or self.path != f.path:
            return False
        if self.code is not None and self.code != f.code:
            return False
        if self.contains is not None and self.contains not in f.message:
            return False
        return True


# ---------------------------------------------------------------------------
# waivers.toml loading
# ---------------------------------------------------------------------------


def load_waivers(path: Path) -> List[Waiver]:
    """Load ``waivers.toml`` (missing file = no waivers)."""
    if not path.exists():
        return []
    entries = tomllib.loads(path.read_text()).get("waiver", [])
    waivers = []
    for i, e in enumerate(entries):
        unknown = set(e) - {"rule", "path", "reason", "code", "contains"}
        if unknown:
            raise ValueError(
                f"waiver #{i + 1}: unknown key(s) {sorted(unknown)}"
            )
        for req in ("rule", "path", "reason"):
            if not e.get(req):
                raise ValueError(f"waiver #{i + 1}: missing required {req!r}")
        waivers.append(
            Waiver(
                rule=str(e["rule"]),
                path=str(e["path"]),
                reason=str(e["reason"]),
                code=e.get("code"),
                contains=e.get("contains"),
            )
        )
    return waivers


def apply_waivers(
    findings: List[Finding], waivers: List[Waiver]
) -> List[Finding]:
    """Mark findings matched by a waiver (first match wins, use counted)."""
    for f in findings:
        for w in waivers:
            if w.matches(f):
                f.waived = True
                f.waiver_reason = w.reason
                w.used += 1
                break
    return findings
