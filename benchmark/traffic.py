"""The one general traffic generator: request streams from a traffic file.

A traffic file (``benchmark/traffic/<name>.json``) holds parameters only:
the loop (``mode``, ``clients``), the cache bypass, the row-drawing rule and
a list of PQL templates with shares. A template's ``draw`` says how each
``{placeholder}`` is filled:

  {"choice": ["Intersect", "Union"]}   uniform over the list
  {"row": "f"}                         a row of field f, by the mix's rule
  {"column": "uniform"}                a column, uniform over all shards

Every client's stream is drawn from the seed before the load, so the
catalogue of distinct requests is finite and known to the reference.
"""

from __future__ import annotations

import string
from dataclasses import dataclass

import numpy as np

MODES = ("closed",)
_FORMATTER = string.Formatter()


class TrafficError(ValueError):
    """A traffic file the generator cannot run."""


@dataclass(frozen=True)
class Template:
    index: int
    pql: str
    share: float
    draw: dict
    #: placeholder names in the order they appear in the text.
    names: tuple

    @property
    def choice_names(self) -> tuple:
        return tuple(n for n in self.names if "choice" in self.draw[n])

    @property
    def value_names(self) -> tuple:
        return tuple(n for n in self.names if "choice" not in self.draw[n])

    def is_write(self) -> bool:
        return self.pql.lstrip().startswith(("Set(", "Clear("))


@dataclass(frozen=True)
class Request:
    """One request: a template, its drawn operator names and its drawn
    numbers. ``group`` (template index + operators) is one program
    structure; requests of a group differ in rows or columns only."""
    template: int
    choices: tuple
    values: tuple
    pql: str

    @property
    def group(self) -> tuple:
        return (self.template, self.choices)


def read_templates(spec: dict) -> list[Template]:
    """Validate a traffic file's templates."""
    mode = spec.get("mode", "closed")
    if mode not in MODES:
        raise TrafficError(
            f"traffic mode {mode!r} is not built yet: this harness drives "
            f"{MODES} only (open-loop arrivals need a sweep on the chip "
            f"first; see PERF.md, Open questions)")
    out = []
    for i, t in enumerate(spec["templates"]):
        names = []
        for _, name, _, _ in _FORMATTER.parse(t["pql"]):
            if name is not None and name not in names:
                names.append(name)
        draw = t.get("draw", {})
        for n in names:
            rule = draw.get(n)
            if not isinstance(rule, dict) or len(rule) != 1 or \
                    next(iter(rule)) not in ("choice", "row", "column"):
                raise TrafficError(
                    f"template {i}: placeholder {{{n}}} needs one of "
                    f"choice/row/column in 'draw', got {rule!r}")
        out.append(Template(i, t["pql"], float(t["share"]), draw,
                            tuple(names)))
    total = sum(t.share for t in out)
    if not out or abs(total - 1.0) > 1e-9:
        raise TrafficError(f"template shares sum to {total}, not 1")
    return out


def render(t: Template, filled: dict) -> str:
    return t.pql.format(**filled)


def structure_text(t: Template, choices: tuple) -> str:
    """The template with its operators filled in and its numbers left
    as ``{name}``: what the reference parses once per group."""
    filled = dict(zip(t.choice_names, choices))
    filled.update({n: "{" + n + "}" for n in t.value_names})
    return t.pql.format(**filled)


class RowPicker:
    """Rows of each field by Zipf over a seeded rank order: the seed
    says which rows are hot, never how hot the hottest is."""

    def __init__(self, spec: dict, fields: dict, seed: int):
        rule = spec.get("row_draw", {"rule": "uniform"})
        if rule["rule"] not in ("zipf", "uniform"):
            raise TrafficError(f"row_draw rule {rule['rule']!r} unknown")
        s = float(rule.get("exponent", 0.0)) if rule["rule"] == "zipf" \
            else 0.0
        self.order, self.p = {}, {}
        for name, f in sorted(fields.items()):
            n = int(f["rows"])
            rng = np.random.default_rng([seed, 0x726F77, *name.encode()])
            self.order[name] = rng.permutation(n)
            w = 1.0 / np.arange(1, n + 1) ** s
            self.p[name] = w / w.sum()

    def draw(self, rng, field: str, n: int) -> np.ndarray:
        if field not in self.order:
            raise TrafficError(f"traffic draws rows of field {field!r}, "
                               f"which the configuration lacks")
        ranks = rng.choice(len(self.p[field]), size=n, p=self.p[field])
        return self.order[field][ranks]


def draw_stream(templates: list[Template], picker: RowPicker, n_columns: int,
                seed: int, stream: int, n: int) -> list[Request]:
    """``n`` requests of stream number ``stream`` (a client, or a
    warm-up pass), a pure function of the seed."""
    rng = np.random.default_rng([seed, 0x73747265, stream])
    which = rng.choice(len(templates), size=n,
                       p=[t.share for t in templates])
    drawn = {}
    for t in templates:
        cols = {}
        for name in t.names:
            rule = t.draw[name]
            if "choice" in rule:
                cols[name] = rng.integers(0, len(rule["choice"]), size=n)
            elif "row" in rule:
                cols[name] = picker.draw(rng, rule["row"], n)
            else:
                cols[name] = rng.integers(0, n_columns, size=n)
        drawn[t.index] = cols
    out = []
    for i, ti in enumerate(which.tolist()):
        t = templates[ti]
        cols = drawn[ti]
        choices = tuple(t.draw[nm]["choice"][int(cols[nm][i])]
                        for nm in t.choice_names)
        values = tuple(int(cols[nm][i]) for nm in t.value_names)
        filled = dict(zip(t.choice_names, choices))
        filled.update(zip(t.value_names, values))
        out.append(Request(ti, choices, values, render(t, filled)))
    return out


def structures(templates: list[Template]) -> list[tuple]:
    """Every (template, operators) pair the mix can produce: one
    compiled program structure each."""
    out = []
    for t in templates:
        combos = [()]
        for nm in t.choice_names:
            combos = [c + (v,) for c in combos for v in t.draw[nm]["choice"]]
        out.extend((t.index, c) for c in combos)
    return out
