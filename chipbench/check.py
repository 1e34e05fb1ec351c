"""What decides ``correct``.

Two comparisons, both after the window has closed:

1. The Processor.  Every query due in the window finished, every node of
   it has an answer, each tool node's answer is what the tool gives for
   the same call run alone, and each LLM node's answer is the text of
   the tokens the engine served for that node's prompt.
2. The model.  For a sample of the finished queries, drawn from the seed
   and holding the one with the longest prompts, every served token of
   every LLM node is scored by the plain float32 reference of the
   configuration's architecture (``arch/<model_type>.py``
   ``logits_at``) over the same prompt and served tokens:
   ``gap_sd`` is the widest distance, in standard deviations of that
   position's logits, by which a served token's reference logit lies
   below the reference's best.  Served tokens are greedy, so a faithful
   step puts it at rounding.

Prompts are rebuilt here from the template text, the bindings and the
answers the Processor gave, with a copy of the system's word-hash
tokenizer, so that each LLM call can be found among the requests the
engines served.
"""
from __future__ import annotations

import hashlib
import re
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

_DIRECTIVE = re.compile(r"\{\{\s*(sql|http|fn)\s*:\s*(.*?)\s*\}\}", re.S)
_REF = re.compile(r"\$\{([A-Za-z_][A-Za-z0-9_/]*)\}")

BOS = 1
_RESERVED = 8


# ----------------------------------------------------- templates as run
def decouple(workflow: dict) -> Dict[str, dict]:
    """Node id -> node dict of a workflow with its embedded tool
    directives pulled out as tool nodes (``<llm id>__<op><i>``), the way
    the Processor's parser names them."""
    nodes: Dict[str, dict] = {}
    for nd in workflow["nodes"]:
        if nd.get("type", "llm") != "llm":
            nodes[nd["id"]] = dict(nd)
            continue
        idx = 0
        out = dict(nd)

        def sub(m, nid=nd["id"]):
            nonlocal idx
            tid = f"{nid}__{m.group(1)}{idx}"
            idx += 1
            nodes[tid] = {"id": tid, "type": "tool", "op": m.group(1),
                          "args": m.group(2)}
            return "${" + tid + "}"

        out["prompt"] = _DIRECTIVE.sub(sub, nd.get("prompt", ""))
        nodes[nd["id"]] = out
    return nodes


def render(text: str, binding: Dict[str, str],
           answers: Dict[str, str]) -> str:
    out = _REF.sub(lambda m: answers.get(m.group(1), m.group(0)), text)
    for key in sorted(binding, key=len, reverse=True):
        out = out.replace("$" + key, str(binding[key]))
    return out


def tokenize(text: str, vocab: int) -> Tuple[int, ...]:
    def word(w):
        h = hashlib.blake2b(w.encode("utf-8"), digest_size=8).digest()
        return _RESERVED + int.from_bytes(h, "little") % (vocab - _RESERVED)
    return (BOS,) + tuple(word(w) for w in text.split())


def detokenize(tokens: Sequence[int]) -> str:
    return " ".join(f"t{t}" for t in tokens)


# ------------------------------------------------------ the Processor
def processor_check(queries: Sequence[dict], templates: Dict[str, dict],
                    served: Dict[Tuple[Tuple[int, ...], int], List[int]],
                    run_tool, vocab: int, sample: Sequence[int]
                    ) -> Tuple[Dict[str, int], List[Tuple[Tuple[int, ...],
                                                          List[int]]]]:
    """Counts of unanswered nodes, tool answers that differ from the tool
    run alone, and LLM answers that differ from the engine's served
    tokens (or have no served request); plus the (prompt, served tokens)
    of the sampled queries' LLM calls for the reference."""
    counts = {"unanswered": 0, "tool_mismatch": 0, "llm_mismatch": 0}
    calls = []
    in_sample = set(sample)
    for i, q in enumerate(queries):
        nodes = decouple(templates[q["template"]])
        answers = q["answers"]
        for nid, nd in nodes.items():
            if not answers.get(nid):
                counts["unanswered"] += 1
        if i not in in_sample:
            continue
        for nid, nd in nodes.items():
            got = answers.get(nid)
            if got is None:
                continue
            if nd.get("type") == "llm":
                prompt = tokenize(render(nd["prompt"], q["binding"],
                                         answers), vocab)
                toks = served.get((prompt, int(nd["max_new_tokens"])))
                if toks is None or detokenize(toks) != got:
                    counts["llm_mismatch"] += 1
                else:
                    calls.append((prompt, toks))
            else:
                args = render(nd["args"], q["binding"], answers)
                if run_tool(nd["op"], args) != got:
                    counts["tool_mismatch"] += 1
    return counts, calls


def sample_queries(queries: Sequence[dict], k: int, seed: int) -> List[int]:
    """``k`` query indices drawn from the seed, always holding the query
    whose bindings are longest (the longest prompts)."""
    if not queries:
        return []
    longest = max(range(len(queries)), key=lambda i: sum(
        len(v.split()) for v in queries[i]["binding"].values()))
    rng = np.random.default_rng([int(seed) % (1 << 64), 3])
    rest = [i for i in range(len(queries)) if i != longest]
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[j] for j in sorted(pick)]


# ------------------------------------------------------------ the model
def model_gap(arch, params, dims, calls, fp8: bool = False
              ) -> Tuple[Optional[float], int, Optional[float]]:
    """(widest gap in standard deviations over every served token of
    ``calls``, tokens scored, widest gap in raw logits), scored by
    ``arch.logits_at``, the reference of the configuration's
    architecture.  With ``fp8`` the control: at each position the token
    the fp8 forward puts first is scored by the float32 reference
    instead of the served token."""
    seqs = [(list(p) + list(t[:-1]), len(p) - 1) for p, t in calls]
    if not seqs:
        return None, 0, None
    ref = arch.logits_at(params, dims, seqs)
    low = arch.logits_at(params, dims, seqs, fp8=True) if fp8 else None
    worst, worst_raw, n = 0.0, 0.0, 0
    for j, (_, toks) in enumerate(calls):
        picked = (low[j].argmax(-1)[:len(toks)] if fp8 else
                  np.asarray(toks))
        r = ref[j][:len(toks)]
        gap = r.max(-1) - r[np.arange(len(picked)), picked]
        worst = max(worst, float((gap / r.std(-1)).max()))
        worst_raw = max(worst_raw, float(gap.max()))
        n += len(toks)
    return worst, n, worst_raw
