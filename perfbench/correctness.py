"""The comparison that decides ``correct``: probes through the served path,
outside the window, against goldens captured on the chip.

(a) every request that finished in the window returned exactly
    ``max_tokens`` tokens, ``finish_reason: length`` and a well-formed
    stream (``client.Record.ok``; decided in ``cli``);
(b) the configuration's probe prompts, served alone and greedily with
    ``logprobs: 5``: at each position up to and including the first token
    mismatch, the golden top-1 id is among the served top-5 and its
    log-probability agrees within the tolerance written in the golden file;
(c) the first probe repeated after the window gives the same ids as before.
"""

from __future__ import annotations

import json
import random
from pathlib import Path
from typing import Optional

PROBE_LENGTHS = (24, 600)
PROBE_TOKENS = 8
TOP_N = 5


def default_prompts(vocab: int, max_len: int) -> list:
    r = random.Random("probes")
    return [[r.randrange(3, vocab) for _ in range(min(n, max_len - 16))]
            for n in PROBE_LENGTHS]


def load_golden(path: Path) -> Optional[dict]:
    if path is None or not Path(path).is_file():
        return None
    return json.loads(Path(path).read_text())


def _id(token: str) -> int:
    return int(token.split(":", 1)[1])


async def probe(client, prompt: list) -> dict:
    out = await client.complete({
        "prompt": prompt, "max_tokens": PROBE_TOKENS, "temperature": 0,
        "logprobs": TOP_N, "return_tokens_as_token_ids": True})
    choice = out["choices"][0]
    lp = choice["logprobs"]
    usage_ok = (out["usage"]["completion_tokens"] == PROBE_TOKENS
                and out["usage"]["prompt_tokens"] == len(prompt)
                and choice["finish_reason"] == "length")
    return {"tokens": [_id(t) for t in lp["tokens"]],
            "logprobs": list(lp["token_logprobs"]),
            "top": [{str(_id(t)): v for t, v in d.items()}
                    for d in lp["top_logprobs"]],
            "usage_ok": usage_ok}


async def run_probes(client, golden: Optional[dict], vocab: int = 0,
                     max_len: int = 0, only_first: bool = False) -> list:
    prompts = [p["prompt"] for p in golden["probes"]] if golden \
        else default_prompts(vocab, max_len)
    if only_first:
        prompts = prompts[:1]
    return [await probe(client, p) for p in prompts]


def compare(golden: Optional[dict], served: list, where: str = "") -> list:
    """Problems found; an empty list means the probes agree. ``served`` may
    hold fewer probes than the golden file (the first ones)."""
    if golden is None:
        return ["no golden file for this configuration"]
    tol = float(golden["tolerance_logprob"])
    problems = []
    for k, (g, s) in enumerate(zip(golden["probes"], served)):
        k = f"{k}{where}"
        if not s["usage_ok"] or len(s["tokens"]) != len(g["tokens"]):
            problems.append(f"probe {k}: wrong length, usage or finish")
            continue
        for i, g_id in enumerate(g["tokens"]):
            got = s["top"][i].get(str(g_id))
            if got is None:
                problems.append(f"probe {k} position {i}: golden id {g_id} "
                                f"is not in the served top-{TOP_N}")
            elif abs(got - g["logprobs"][i]) > tol:
                problems.append(
                    f"probe {k} position {i}: logprob {got:.4f} against "
                    f"golden {g['logprobs'][i]:.4f} (tolerance {tol})")
            if s["tokens"][i] != g_id:
                break       # contexts differ from here on
    return problems


def max_logprob_gap(a: dict, b: dict) -> float:
    """Largest |logprob difference| of run b against run a's top-1 ids, up
    to the first token mismatch (how goldens' tolerance is chosen)."""
    worst = 0.0
    for i, g_id in enumerate(a["tokens"]):
        got = b["top"][i].get(str(g_id))
        if got is not None:
            worst = max(worst, abs(got - a["logprobs"][i]))
        if b["tokens"][i] != g_id:
            break
    return worst
