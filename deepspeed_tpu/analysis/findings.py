"""Shared findings / severity / suppression model for dslint (ISSUE 6).

Both analysis engines — the AST linter (``ast_rules``) and the HLO program
verifier (``hlo_rules``) — report through one :class:`Finding` shape so the
CLI, the baseline file and the pytest gate all consume a single
stream. A finding is identified across runs by its :meth:`Finding.fingerprint`
— rule + file (or pseudo-path ``hlo://<program>``) + enclosing symbol + a
hash of the offending line text — deliberately NOT the line number, so a
baseline survives unrelated edits above the finding.

Suppression: a ``# dslint: disable=<rule>[,<rule>...]`` comment on the
flagged line or the line directly above it silences that rule there (bare
``# dslint: disable`` silences every rule). Suppressions are counted, not
hidden: the analyzer reports how many findings were waived so a PR review
can see the justifications grow.
"""

from __future__ import annotations

import hashlib
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

SEVERITY_ERROR = "error"
SEVERITY_WARNING = "warning"

_DISABLE = re.compile(r"#\s*dslint:\s*disable(?:=(?P<rules>[\w\-, ]+))?")


@dataclass
class Finding:
    """One rule violation, from either engine."""

    rule: str
    severity: str
    message: str
    path: str = ""        # source file, or "hlo://<program>" for Engine A
    line: int = 0         # 1-based line in the source / HLO text
    symbol: str = ""      # enclosing function qualname or HLO computation
    snippet: str = ""     # the offending line, stripped
    engine: str = "ast"   # "ast" | "hlo"

    def fingerprint(self) -> str:
        digest = hashlib.sha1(self.snippet.strip().encode()).hexdigest()[:12]
        return f"{self.rule}|{self.path}|{self.symbol}|{digest}"

    def to_dict(self) -> Dict:
        return {
            "rule": self.rule,
            "severity": self.severity,
            "message": self.message,
            "path": self.path,
            "line": self.line,
            "symbol": self.symbol,
            "snippet": self.snippet,
            "engine": self.engine,
            "fingerprint": self.fingerprint(),
        }

    def render(self) -> str:
        loc = f"{self.path}:{self.line}" if self.line else self.path
        sym = f" [{self.symbol}]" if self.symbol else ""
        return f"{loc}: {self.severity}: {self.rule}: {self.message}{sym}"


def _disabled_rules(line: str) -> Optional[set]:
    """Rules disabled by a ``# dslint: disable`` comment on ``line``;
    ``set()`` means "all rules", None means no suppression comment."""
    m = _DISABLE.search(line)
    if not m:
        return None
    rules = m.group("rules")
    if rules is None:
        return set()
    return {r.strip() for r in rules.split(",") if r.strip()}


@dataclass
class SuppressionIndex:
    """Per-file map of line → suppressed rules, built once from source.

    An inline comment suppresses its own line. A comment-only line
    suppresses the next code line, scanning past further comment lines —
    so a multi-line justification block above the statement works."""

    # line → set of rule names, or None meaning "all rules"
    by_line: Dict[int, Optional[set]] = field(default_factory=dict)

    def _register(self, line: int, rules: set) -> None:
        if not rules:  # bare "# dslint: disable" = every rule
            self.by_line[line] = None
        elif self.by_line.get(line, set()) is not None:
            self.by_line.setdefault(line, set()).update(rules)

    @classmethod
    def from_source(cls, text: str) -> "SuppressionIndex":
        idx = cls()
        lines = text.splitlines()
        for i, line in enumerate(lines, start=1):
            rules = _disabled_rules(line)
            if rules is None:
                continue
            idx._register(i, rules)
            if line.lstrip().startswith("#"):
                # standalone comment: also covers the next code line (a
                # justification block may continue over more comment lines)
                for j in range(i + 1, len(lines) + 1):
                    stripped = lines[j - 1].strip()
                    if stripped and not stripped.startswith("#"):
                        idx._register(j, rules)
                        break
        return idx

    def suppresses(self, rule: str, line: int) -> bool:
        for ln in (line, line - 1):
            if ln in self.by_line:
                rules = self.by_line[ln]
                if rules is None or rule in rules:
                    return True
        return False


def apply_suppressions(
    findings: Iterable[Finding], index: SuppressionIndex
) -> Tuple[List[Finding], int]:
    """→ (kept findings, suppressed count)."""
    kept, waived = [], 0
    for f in findings:
        if index.suppresses(f.rule, f.line):
            waived += 1
        else:
            kept.append(f)
    return kept, waived
