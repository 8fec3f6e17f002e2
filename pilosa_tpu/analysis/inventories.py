"""Inventory diffs: env gates, config knobs and event types vs
docs/operations.md.

Three drift guards that complement the stats-registry guard in
tests/test_metrics_conformance.py:

* env gates — every `PILOSA_TPU_*` name referenced anywhere under
  pilosa_tpu/ must appear in docs/operations.md, so an operator reading
  the env-var table sees the complete gate surface; and every name that
  table lists must still be referenced under pilosa_tpu/, so a switch
  deleted from the code does not live on in the documents.
* config knobs — every field of every `[section]` dataclass in
  cli/config.py must appear (kebab-case) BOTH in docs/operations.md and
  in `Config.to_toml()` (the serialization a knob must ride to be
  wired cli→config→Server; a field missing there is a knob that cannot
  round-trip through `pilosa-tpu config`).
* event types — every string-literal type passed to a flight-recorder
  `journal.emit(...)` must be registered in utils/events.py EVENT_TYPES
  (it would raise at runtime otherwise — this catches it statically),
  and every REGISTERED type must appear in the docs/operations.md event
  glossary, so the timeline an operator reads is fully documented. The
  literal-only half is the `event-registry` lint rule.
"""

from __future__ import annotations

import ast
import dataclasses
import os
import re
from typing import Optional

from pilosa_tpu.analysis.lint import (
    Finding,
    _is_event_emit_call,
    iter_py_files,
)

_ENV_TOKEN = re.compile(r"PILOSA_TPU_[A-Z0-9_]*[A-Z0-9]")
# a row of the docs' env table: the name, in backticks, in the first cell
_ENV_TABLE_ROW = re.compile(r"^\|\s*`(" + _ENV_TOKEN.pattern + ")`")


def _read(path: str) -> str:
    with open(path, encoding="utf-8") as f:
        return f.read()


def env_gate_inventory(root: str) -> dict[str, tuple[str, int]]:
    """{env name: (relpath, first line referencing it)} over pilosa_tpu/."""
    out: dict[str, tuple[str, int]] = {}
    for path in iter_py_files(root):
        rel = os.path.relpath(path, root).replace(os.sep, "/")
        for lineno, line in enumerate(_read(path).splitlines(), 1):
            for m in _ENV_TOKEN.finditer(line):
                out.setdefault(m.group(0), (rel, lineno))
    return out


def _read_docs(root: str) -> Optional[str]:
    path = os.path.join(root, "docs", "operations.md")
    if not os.path.exists(path):
        return None
    return _read(path)


def env_gate_findings(root: str) -> list[Finding]:
    docs = _read_docs(root)
    if docs is None:
        return [Finding("docs/operations.md", 0, "env-gate-docs",
                        f"docs/operations.md not found under {root}; "
                        "pass --root <repo root>")]
    findings = []
    inventory = env_gate_inventory(root)
    for name, (rel, lineno) in sorted(inventory.items()):
        if name not in docs:
            findings.append(Finding(
                rel, lineno, "env-gate-docs",
                f"env gate {name} is read in code but undocumented in "
                "docs/operations.md"))
    for lineno, line in enumerate(docs.splitlines(), 1):
        m = _ENV_TABLE_ROW.match(line)
        if m and m.group(1) not in inventory:
            findings.append(Finding(
                "docs/operations.md", lineno, "env-gate-docs",
                f"env gate {m.group(1)} is listed in the env table but "
                "nothing under pilosa_tpu/ reads it"))
    return findings


def config_knob_inventory() -> list[tuple[str, str]]:
    """[(section, kebab-knob)] from the Config dataclass tree; the
    top-level scalars report section ""."""
    from pilosa_tpu.cli.config import Config

    knobs: list[tuple[str, str]] = []
    cfg = Config()
    for f in dataclasses.fields(Config):
        sub = getattr(cfg, f.name)
        if dataclasses.is_dataclass(sub):
            section = f.name.replace("_", "-")
            for sf in dataclasses.fields(type(sub)):
                knobs.append((section, sf.name.replace("_", "-")))
        else:
            knobs.append(("", f.name.replace("_", "-")))
    return knobs


def event_type_inventory(root: str) -> dict[str, tuple[str, int]]:
    """{event type literal: (relpath, first emitting line)} collected
    from every `<journal|events>.emit("<literal>", ...)` call (and the
    `._journal_emit` forwarding shims) under pilosa_tpu/ — the
    event-registry lint rule guarantees literals."""
    out: dict[str, tuple[str, int]] = {}
    for path in iter_py_files(root):
        rel = os.path.relpath(path, root).replace(os.sep, "/")
        try:
            tree = ast.parse(_read(path))
        except SyntaxError:
            continue  # the lint pass reports this
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and _is_event_emit_call(node)):
                continue
            first = node.args[0] if node.args else None
            if isinstance(first, ast.Constant) \
                    and isinstance(first.value, str):
                out.setdefault(first.value, (rel, node.lineno))
    return out


def event_type_findings(root: str) -> list[Finding]:
    """The event-registry inventory diff: emitted-but-unregistered types
    (a runtime ValueError waiting to fire) and registered-but-
    undocumented types (a timeline the operator can't decode)."""
    from pilosa_tpu.utils.events import EVENT_TYPES

    docs = _read_docs(root)
    if docs is None:
        return [Finding("docs/operations.md", 0, "event-registry-docs",
                        f"docs/operations.md not found under {root}; "
                        "pass --root <repo root>")]
    findings = []
    used = event_type_inventory(root)
    for name, (rel, lineno) in sorted(used.items()):
        if name not in EVENT_TYPES:
            findings.append(Finding(
                rel, lineno, "event-registry",
                f"event type {name!r} is emitted but not registered in "
                "utils/events.py EVENT_TYPES (emit() will raise)"))
    for name in sorted(EVENT_TYPES):
        if name not in docs:
            findings.append(Finding(
                "pilosa_tpu/utils/events.py", 0, "event-registry-docs",
                f"registered event type {name} is missing from the "
                "docs/operations.md event glossary"))
    return findings


def config_knob_findings(root: str) -> list[Finding]:
    from pilosa_tpu.cli.config import Config

    docs = _read_docs(root)
    if docs is None:
        return [Finding("docs/operations.md", 0, "config-knob-docs",
                        f"docs/operations.md not found under {root}; "
                        "pass --root <repo root>")]
    toml = Config().to_toml()
    cfg_rel = "pilosa_tpu/cli/config.py"
    findings = []
    for section, knob in config_knob_inventory():
        label = f"[{section}] {knob}" if section else knob
        if knob not in docs:
            findings.append(Finding(
                cfg_rel, 0, "config-knob-docs",
                f"knob {label} is undocumented in docs/operations.md"))
        if knob not in toml:
            findings.append(Finding(
                cfg_rel, 0, "config-knob-wiring",
                f"knob {label} missing from Config.to_toml() — it cannot "
                "round-trip through `pilosa-tpu config`"))
    return findings
