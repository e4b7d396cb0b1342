"""Pass framework for the h2o3_tpu static analyzer.

The analyzer walks the repo's own sources (``ast`` only — importing this
module must never pull jax or any runtime module, so ``--changed-only``
runs stay fast) and reports :class:`Finding`\\ s keyed by a stable rule id.

Three suppression layers, in order of preference:

1. fix the code;
2. an inline ``# h2o3: noqa[RULE]`` comment on the flagged line (or the
   line directly above it) for sites that are *intentionally* in
   violation — the comment documents the exception next to the code;
3. an entry in the checked-in JSON baseline (``analysis_baseline.json``)
   with a one-line justification, for accepted pre-existing findings
   that should not block the build but also should not be silently
   blessed in-source.

Baseline entries match on a content fingerprint (rule + file + enclosing
symbol + stripped source line), not on line numbers, so unrelated edits
above a baselined site do not invalidate it.
"""

from __future__ import annotations

import ast
import hashlib
import json
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Set

#: directories/files under the repo root the analyzer scans by default
DEFAULT_ROOTS = ("h2o3_tpu", "scripts")

#: path fragments never analyzed (generated/vendored/fixture code)
EXCLUDE_PARTS = ("tests/", "h2o3r/", "deploy/", "/.", "__pycache__")

_NOQA_RE = re.compile(r"#\s*h2o3:\s*noqa(?:\[([A-Za-z0-9_,\s]+)\])?")


@dataclass
class Finding:
    """One rule violation at a specific site."""

    rule: str
    file: str          #: repo-relative path
    line: int          #: 1-based
    symbol: str        #: enclosing ``Class.method`` / function qualname, or ""
    message: str
    snippet: str = ""  #: stripped source of the flagged line

    @property
    def fingerprint(self) -> str:
        """Line-number-independent identity used by the baseline."""
        raw = "|".join((self.rule, self.file, self.symbol, self.snippet))
        return hashlib.sha1(raw.encode()).hexdigest()[:16]

    def to_dict(self) -> dict:
        return {
            "rule": self.rule,
            "file": self.file,
            "line": self.line,
            "symbol": self.symbol,
            "message": self.message,
            "snippet": self.snippet,
            "fingerprint": self.fingerprint,
        }

    def render(self) -> str:
        sym = f" [{self.symbol}]" if self.symbol else ""
        return f"{self.file}:{self.line}: {self.rule}{sym} {self.message}"


@dataclass
class Module:
    """A parsed source file plus the suppression map derived from it."""

    path: str                      #: absolute path
    rel: str                       #: repo-relative path
    source: str
    tree: ast.Module
    lines: List[str] = field(default_factory=list)
    #: line -> set of rule ids suppressed there ({"*"} = all rules)
    noqa: Dict[int, Set[str]] = field(default_factory=dict)

    @classmethod
    def parse(cls, path: str, rel: str, source: Optional[str] = None
              ) -> "Module":
        if source is None:
            with open(path, encoding="utf-8") as f:
                source = f.read()
        tree = ast.parse(source, filename=rel)
        lines = source.splitlines()
        noqa: Dict[int, Set[str]] = {}
        for i, text in enumerate(lines, start=1):
            m = _NOQA_RE.search(text)
            if not m:
                continue
            rules = ({"*"} if m.group(1) is None else
                     {r.strip() for r in m.group(1).split(",") if r.strip()})
            noqa.setdefault(i, set()).update(rules)
        return cls(path=path, rel=rel, source=source, tree=tree,
                   lines=lines, noqa=noqa)

    def line_text(self, lineno: int) -> str:
        if 1 <= lineno <= len(self.lines):
            return self.lines[lineno - 1].strip()
        return ""

    def suppressed(self, lineno: int, rule: str) -> bool:
        """True if ``rule`` is noqa'd on the line or the line above it."""
        for ln in (lineno, lineno - 1):
            rules = self.noqa.get(ln)
            if rules and ("*" in rules or rule in rules):
                return True
        return False


def iter_source_files(root: str,
                      roots: Sequence[str] = DEFAULT_ROOTS) -> List[str]:
    """Repo-relative paths of every analyzable ``.py`` file."""
    out: List[str] = []
    for entry in roots:
        full = os.path.join(root, entry)
        if os.path.isfile(full):
            out.append(entry)
            continue
        for dirpath, dirnames, filenames in os.walk(full):
            dirnames[:] = [d for d in dirnames
                           if d != "__pycache__" and not d.startswith(".")]
            for fn in sorted(filenames):
                if not fn.endswith(".py"):
                    continue
                rel = os.path.relpath(os.path.join(dirpath, fn), root)
                rel = rel.replace(os.sep, "/")
                if any(part in rel for part in EXCLUDE_PARTS):
                    continue
                out.append(rel)
    return sorted(set(out))


def load_modules(root: str,
                 files: Optional[Iterable[str]] = None) -> List[Module]:
    """Parse ``files`` (repo-relative; default: the whole scan surface)."""
    rels = list(files) if files is not None else iter_source_files(root)
    mods: List[Module] = []
    for rel in rels:
        path = os.path.join(root, rel)
        if not os.path.isfile(path):
            continue
        try:
            mods.append(Module.parse(path, rel))
        except SyntaxError as e:
            mods.append(Module.parse(
                path, rel, source=""))  # keep slot; surface as a finding
            mods[-1].noqa = {}
            mods[-1].lines = []
            mods[-1].tree = ast.Module(body=[], type_ignores=[])
            mods[-1].source = ""
            _SYNTAX_ERRORS.append(Finding(
                rule="PARSE001", file=rel, line=e.lineno or 0, symbol="",
                message=f"file does not parse: {e.msg}", snippet=""))
    return mods


_SYNTAX_ERRORS: List[Finding] = []


# ---------------------------------------------------------------------------
# baseline

BASELINE_VERSION = 1


def load_baseline(path: str) -> Dict[str, dict]:
    """fingerprint -> entry dict. Missing file = empty baseline."""
    if not os.path.isfile(path):
        return {}
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    if data.get("version") != BASELINE_VERSION:
        raise ValueError(
            f"baseline {path} has unsupported version {data.get('version')!r}")
    return {e["fingerprint"]: e for e in data.get("entries", [])}


def save_baseline(path: str, findings: Sequence[Finding],
                  justifications: Optional[Dict[str, str]] = None) -> None:
    """Write a baseline accepting ``findings``; keeps prior justifications
    for fingerprints already present when ``justifications`` maps them."""
    justifications = justifications or {}
    entries = []
    for f in sorted(findings, key=lambda f: (f.file, f.line, f.rule)):
        entries.append({
            "fingerprint": f.fingerprint,
            "rule": f.rule,
            "file": f.file,
            "symbol": f.symbol,
            "snippet": f.snippet,
            "justification": justifications.get(
                f.fingerprint, "accepted pre-existing finding"),
        })
    with open(path, "w", encoding="utf-8") as f:
        json.dump({"version": BASELINE_VERSION, "entries": entries}, f,
                  indent=2, sort_keys=False)
        f.write("\n")


def split_baselined(findings: Sequence[Finding], baseline: Dict[str, dict]
                    ) -> tuple:
    """(new, accepted) partition of ``findings`` against the baseline."""
    new, accepted = [], []
    for f in findings:
        (accepted if f.fingerprint in baseline else new).append(f)
    return new, accepted


# ---------------------------------------------------------------------------
# driver


@dataclass
class Context:
    """Shared inputs handed to every pass."""

    root: str
    readme_path: str
    modules: List[Module] = field(default_factory=list)
    #: full-surface module list for cross-module passes (lock ordering,
    #: knob registry) even when only a subset is being re-analyzed
    all_modules: List[Module] = field(default_factory=list)


def default_passes() -> Dict[str, object]:
    """name -> run(ctx) callable for every registered pass (lazy imports
    so a subset run does not pay for the others)."""
    from .passes import (knob_registry, lock_discipline, rpc_payload,
                         seeded_determinism, tracer_purity)

    passes = {
        "lock-discipline": lock_discipline.run,
        "tracer-purity": tracer_purity.run,
        "seeded-determinism": seeded_determinism.run,
        "knob-registry": knob_registry.run,
        "rpc-payload": rpc_payload.run,
    }
    from .passes import telemetry_drift
    passes["telemetry-drift"] = telemetry_drift.run
    return passes


def run_passes(ctx: Context,
               pass_names: Optional[Sequence[str]] = None) -> List[Finding]:
    """Run the requested passes, apply noqa suppressions, sort findings."""
    registry = default_passes()
    names = list(pass_names) if pass_names else list(registry)
    unknown = [n for n in names if n not in registry]
    if unknown:
        raise KeyError(f"unknown pass(es): {', '.join(unknown)}")

    by_rel = {m.rel: m for m in ctx.all_modules or ctx.modules}
    findings: List[Finding] = list(_SYNTAX_ERRORS)
    _SYNTAX_ERRORS.clear()
    for name in names:
        findings.extend(registry[name](ctx))

    kept = []
    for f in findings:
        mod = by_rel.get(f.file)
        if mod is not None and mod.suppressed(f.line, f.rule):
            continue
        kept.append(f)
    kept.sort(key=lambda f: (f.file, f.line, f.rule, f.message))
    return kept


def analyze(root: str, files: Optional[Iterable[str]] = None,
            pass_names: Optional[Sequence[str]] = None,
            readme_path: Optional[str] = None) -> List[Finding]:
    """One-call entry point: parse, run passes, suppress, sort."""
    all_modules = load_modules(root)
    if files is None:
        modules = all_modules
    else:
        wanted = set(files)
        by_rel = {m.rel: m for m in all_modules}
        modules = [by_rel[rel] for rel in sorted(wanted) if rel in by_rel]
        # subset files outside the default scan surface still analyze —
        # and must join all_modules so cross-module passes see them
        extra = load_modules(root, sorted(
            rel for rel in wanted if rel not in by_rel))
        modules.extend(extra)
        all_modules = all_modules + extra
    ctx = Context(root=root,
                  readme_path=readme_path or os.path.join(root, "README.md"),
                  modules=modules, all_modules=all_modules)
    return run_passes(ctx, pass_names)


def analyze_source(source: str, rel: str = "snippet.py",
                   pass_names: Optional[Sequence[str]] = None,
                   readme_text: str = "") -> List[Finding]:
    """Analyze an in-memory snippet — the unit-test entry point.

    ``readme_text`` stands in for README.md for the knob-registry pass.
    """
    mod = Module.parse(rel, rel, source=source)
    ctx = Context(root="", readme_path="", modules=[mod], all_modules=[mod])
    ctx.readme_text = readme_text  # type: ignore[attr-defined]
    names = list(pass_names) if pass_names else [
        "lock-discipline", "tracer-purity", "seeded-determinism",
        "knob-registry", "rpc-payload",
    ]
    return run_passes(ctx, names)
