"""The one traffic generator: a mix file's parameters + a seed -> the
queries a run submits.

A mix (``chipbench/traffic/<name>.json``) names its driver, its database,
its workflow templates (plain JSON, the Processor's input format) and,
per template part, how each query's bindings are drawn.  Every seed
gives the same SET of sizes, in another order, so that runs
differ in content and order and not in the amount of work.

Binding sources (``"bindings"`` of a part, applied in order):

- ``{"kind": "rows", "table": t, "columns": {key: column}}`` — one
  query per row of a block of consecutive rows of ``t``, the block's
  first row drawn from the seed;
- ``{"kind": "choice", "key": k, "values": [...]}`` — uniform choice;
- ``{"kind": "int", "key": k, "lo": a, "hi": b}`` — uniform in [a, b);
- ``{"kind": "text", "key": k, "per": "group"|"query",
  "lengths": [[words, count], ...], "vocab": n, "zipf": s}`` — a text of
  ``words`` words drawn from a Zipf(s) law over ``n`` words.  The
  ``lengths`` counts are exact per batch (per group when ``per`` is
  ``group``), shuffled by the seed.

A part with ``"groups": g`` splits its queries into ``g`` groups of equal
size; a ``per: group`` source gives every query of a group the same
value (documents asked several times).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

Binding = Dict[str, str]

# the seed of the warm-up traffic is the run's seed mixed with this, so
# warm-up and window never draw the same queries
WARMUP_SALT = 0x5EED_0FF5


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 64), *stream])


# the database as the ``rows`` source reads it: {table: (columns, rows)}
Tables = Dict[str, Tuple[Sequence[str], Sequence]]


def _zipf_words(rng, n_words: int, vocab: int, s: float) -> str:
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    p = ranks ** -s
    p /= p.sum()
    ids = rng.choice(vocab, size=n_words, p=p)
    return " ".join(f"w{i}" for i in ids)


def _exact_lengths(rng, lengths, n: int) -> List[int]:
    out = [int(w) for w, c in lengths for _ in range(int(c))]
    if len(out) != n:
        raise ValueError(f"lengths {lengths} give {len(out)} values, "
                         f"the part needs {n}")
    rng.shuffle(out)
    return out


def part_bindings(part: dict, n: int, rng, tables: Optional[Tables]
                  ) -> List[Binding]:
    """``n`` bindings for one template part."""
    groups = int(part.get("groups", n))
    if n % groups:
        raise ValueError(f"{n} queries do not split into {groups} groups")
    per_group = n // groups
    out: List[Binding] = [{} for _ in range(n)]
    for src in part.get("bindings", []):
        kind = src["kind"]
        if kind == "rows":
            cols, rows = tables[src["table"]]
            first = int(rng.integers(0, len(rows) - n + 1))
            for q in range(n):
                row = rows[first + q]
                for key, col in src["columns"].items():
                    out[q][key] = str(row[list(cols).index(col)])
        elif kind == "choice":
            vals = src["values"]
            for q in range(n):
                out[q][src["key"]] = str(vals[int(rng.integers(len(vals)))])
        elif kind == "int":
            for q in range(n):
                out[q][src["key"]] = str(int(rng.integers(src["lo"],
                                                          src["hi"])))
        elif kind == "text":
            per = src.get("per", "query")
            m = groups if per == "group" else n
            lens = _exact_lengths(rng, src["lengths"], m)
            texts = [_zipf_words(rng, w, int(src["vocab"]),
                                 float(src["zipf"])) for w in lens]
            for q in range(n):
                out[q][src["key"]] = texts[q // per_group if per == "group"
                                           else q]
        else:
            raise ValueError(f"unknown binding source {kind!r}")
    return out


def batch(mix: dict, seed: int, index: int, tables: Optional[Tables] = None
          ) -> Tuple[str, List[Binding]]:
    """Batch ``index`` of a closed-loop mix: (template, bindings)."""
    (part,) = mix["parts"]
    n = int(part["queries"])
    rng = rng_for(seed, 1, index)
    return part["template"], part_bindings(part, n, rng, tables)
