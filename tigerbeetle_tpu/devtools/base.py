"""vet infrastructure: passes, violations, and closed JSON baselines.

The reference treats static analysis as part of the build (reference:
src/tidy.zig, src/copyhound.zig — discipline violations are build
failures, not review comments). `scripts/vet.py` is the driver; this
module is the shared machinery every pass builds on:

- `SourceFile`: one parsed source file (text + AST + per-line comments).
- `VetPass`: a named pass with documented checks; `run()` returns
  `Violation`s. Passes never print — the driver owns presentation.
- closed baselines: a pass may carry a JSON baseline of deliberate,
  explained sites. The baseline is CLOSED in both directions — a new
  site fails the run, and a baselined site that no longer exists fails
  too (the old open-set copyhound check let entries rot). Every entry
  carries a mandatory human `why` string; an empty `why` fails the run
  (`--update` writes new entries with an empty `why` precisely so the
  run stays red until a human justifies them).
"""

from __future__ import annotations

import ast
import dataclasses
import json
import pathlib
import re


@dataclasses.dataclass
class Violation:
    file: str  # repo-relative path
    line: int
    pass_name: str
    check: str  # check id within the pass (see VetPass.checks)
    message: str
    # stable baseline key ("" = never baselinable: always a hard failure)
    site: str = ""

    def render(self) -> str:
        return (
            f"{self.file}:{self.line}: [{self.pass_name}/{self.check}] "
            f"{self.message}"
        )


class SourceFile:
    """One source file: text, lines, lazily parsed AST, and the `# noqa`
    / `# vet:` comment maps the passes share."""

    def __init__(self, rel: str, text: str):
        self.rel = rel
        self.text = text
        self.lines = text.splitlines()
        self._tree: ast.AST | None = None
        self._parse_error: SyntaxError | None = None
        self._parsed = False

    @property
    def tree(self) -> ast.AST | None:
        if not self._parsed:
            self._parsed = True
            try:
                self._tree = ast.parse(self.text)
            except SyntaxError as e:
                self._parse_error = e
        return self._tree

    @property
    def parse_error(self) -> SyntaxError | None:
        _ = self.tree  # force the lazy parse
        return self._parse_error

    # the lookbehind skips prose MENTIONS of noqa: documentation quotes
    # the marker in backticks (`# noqa`), real suppressions never do
    _NOQA_RE = re.compile(r"(?<!`)#\s*noqa(?::\s*([A-Za-z0-9_,\s-]+))?")

    def noqa(self) -> dict[int, set[str] | None]:
        """line -> named checks suppressed there, or None for a BARE
        `# noqa` (which tidy reports as its own violation)."""
        out: dict[int, set[str] | None] = {}
        for i, line in enumerate(self.lines, 1):
            m = self._NOQA_RE.search(line)
            if m is None:
                continue
            names = m.group(1)
            if names is None:
                out[i] = None
            else:
                out[i] = {
                    n.strip() for n in names.split(",") if n.strip()
                }
        return out

    _VET_RE = re.compile(r"#\s*vet:\s*(.+?)\s*$")

    def vet_comments(self) -> dict[int, str]:
        """line -> raw `# vet:` declaration text on that line."""
        out: dict[int, str] = {}
        for i, line in enumerate(self.lines, 1):
            m = self._VET_RE.search(line)
            if m is not None:
                out[i] = m.group(1)
        return out


def load_files(root: pathlib.Path, rels: list[str]) -> list[SourceFile]:
    return [
        SourceFile(rel, (root / rel).read_text()) for rel in sorted(rels)
    ]


def discover(root: pathlib.Path) -> list[str]:
    """Repo-relative paths of every Python source the passes scan."""
    rels: list[str] = []
    for base in ("tigerbeetle_tpu", "tests", "scripts"):
        for path in sorted((root / base).rglob("*.py")):
            rels.append(str(path.relative_to(root)))
    if (root / "__graft_entry__.py").exists():
        rels.append("__graft_entry__.py")
    return rels


class VetPass:
    """Base pass. Subclasses set `name`, `checks` (check id -> one-line
    explanation for --explain) and implement run()."""

    name = "base"
    doc = ""
    checks: dict[str, str] = {}
    baseline_name: str | None = None  # file name under scripts/, if any

    def run(self, files: list[SourceFile], config) -> list[Violation]:
        raise NotImplementedError


# ----------------------------------------------------------------------
# closed baselines
# ----------------------------------------------------------------------

BASELINE_VERSION = 2


def load_baseline(path: pathlib.Path) -> dict[str, dict]:
    """site -> {count, why}. Missing file = empty baseline."""
    if not path.exists():
        return {}
    raw = json.loads(path.read_text())
    if "version" not in raw:
        # v1 (open-set, why-less) schema: {rel: {kind: count}} — lift it
        # so --update can carry counts; every entry still needs a why
        # before the run goes green
        return {
            f"{rel}::{kind}": {
                "site": f"{rel}::{kind}", "count": n, "why": "",
            }
            for rel, kinds in raw.items()
            for kind, n in kinds.items()
        }
    assert raw.get("version") == BASELINE_VERSION, (
        f"{path.name}: expected baseline version {BASELINE_VERSION} "
        f"(run scripts/vet.py --update to migrate)"
    )
    return {e["site"]: e for e in raw["entries"]}


def save_baseline(path: pathlib.Path, sites: dict[str, int],
                  old: dict[str, dict]) -> int:
    """Write the v2 baseline for the observed `site -> count` map,
    carrying over existing `why` strings. Returns the number of entries
    left with an empty why (the run stays red until a human fills them).
    """
    entries = []
    unexplained = 0
    for site in sorted(sites):
        why = old.get(site, {}).get("why", "")
        if not why:
            unexplained += 1
        entries.append({"site": site, "count": sites[site], "why": why})
    path.write_text(
        json.dumps(
            {"version": BASELINE_VERSION, "entries": entries},
            indent=1,
            sort_keys=True,
        )
        + "\n"
    )
    return unexplained


def apply_baseline(
    pass_name: str,
    violations: list[Violation],
    baseline: dict[str, dict],
    baseline_file: str,
) -> list[Violation]:
    """Filter `violations` through a closed baseline.

    - a site whose count matches its entry is suppressed;
    - a count above the entry reports the excess as NEW sites;
    - a count below the entry (or a site gone entirely) reports the
      entry as STALE — the baseline must shrink with the code;
    - an entry with an empty `why` always fails."""
    out: list[Violation] = []
    counts: dict[str, list[Violation]] = {}
    for v in violations:
        if v.site:
            counts.setdefault(v.site, []).append(v)
        else:
            out.append(v)
    for site, vs in sorted(counts.items()):
        entry = baseline.get(site)
        if entry is None:
            out.extend(vs)
            continue
        if not entry.get("why"):
            out.append(
                Violation(
                    baseline_file, 0, pass_name, "baseline-why",
                    f"baseline entry {site!r} has no `why` — every "
                    "deliberate site needs a human justification",
                )
            )
        if len(vs) > entry["count"]:
            for v in vs[entry["count"]:]:
                v.message += (
                    f" ({len(vs)} sites vs {entry['count']} baselined)"
                )
                out.append(v)
        elif len(vs) < entry["count"]:
            out.append(
                Violation(
                    baseline_file, 0, pass_name, "baseline-stale",
                    f"baseline entry {site!r} expects {entry['count']} "
                    f"site(s) but only {len(vs)} exist — re-baseline "
                    "with --update (the baseline is closed)",
                )
            )
    for site, entry in sorted(baseline.items()):
        if site not in counts:
            out.append(
                Violation(
                    baseline_file, 0, pass_name, "baseline-stale",
                    f"baseline entry {site!r} matches nothing — the "
                    "site was removed; re-baseline with --update",
                )
            )
    return out


# ----------------------------------------------------------------------
# small AST helpers shared by passes
# ----------------------------------------------------------------------


def dotted(node: ast.AST) -> str | None:
    """'a.b.c' for a Name/Attribute chain, else None."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def self_attr(node: ast.AST) -> str | None:
    """'x' when node is exactly `self.x`."""
    if (
        isinstance(node, ast.Attribute)
        and isinstance(node.value, ast.Name)
        and node.value.id == "self"
    ):
        return node.attr
    return None
