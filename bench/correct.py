"""The comparison that decides ``correct`` for a served model.

After the window has closed and the program's state is freed, a sample of the
finished requests, drawn from the seed and holding the longest of them, is run
once through the plain reference over prompt + served tokens. At each served
position the number compared is the gap by which the served token's reference
logit lies below the reference's best, in standard deviations of that
position's reference logits. The widest gap over the sample must not pass the
configuration's limit. Greedy decoding serves the argmax, so a sound engine
only ever misses the best by rounding; a wrong token reads several deviations.

The control is the same reference in float8 (``precision="fp8"``): at the
same positions it reads the gap of the token that float8 puts first.
"""
from __future__ import annotations

import importlib.util
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_reference(name: str):
    path = os.path.join(HERE, "reference", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_ref_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def pick_sample(done: dict, rng: np.random.Generator, *, tokens: int,
                must: tuple = ()) -> list:
    """Ids of finished requests to compare: the longest (prompt + served),
    every id in ``must`` that finished, then seeded picks until the sample
    holds ``tokens`` served tokens."""
    ids = sorted(done)
    if not ids:
        return []
    longest = max(ids, key=lambda i: (len(done[i][0]) + len(done[i][1]), i))
    out = [longest] + [i for i in sorted(set(must)) if i in done
                       and i != longest]
    rest = [i for i in rng.permutation(ids).tolist() if i not in out]
    served = sum(len(done[i][1]) for i in out)
    for i in rest:
        if served >= tokens:
            break
        out.append(i)
        served += len(done[i][1])
    return out


def compare(ref, params, cfg: dict, seqs: list, *, control: bool = False,
            batch: int = 4) -> dict:
    """``seqs``: list of ``(prompt, served tokens)``. Returns the widest gap
    of the served tokens (``gap``) and, with ``control``, the widest gap of
    the tokens float8 puts first (``control_gap``), plus counts."""
    worst, worst_c, agree, n = 0.0, 0.0, 0, 0
    order = sorted(range(len(seqs)), key=lambda i: -len(seqs[i][0])
                   - len(seqs[i][1]))
    for lo in range(0, len(order), batch):
        part = [seqs[i] for i in order[lo:lo + batch]]
        # fixed shapes (rows of ``batch``, widths in steps of 256), so the
        # reference compiles once per width and the compile cache serves it
        width = -(-max(len(p) + len(t) for p, t in part) // 256) * 256
        toks = np.zeros((batch, width), np.int32)
        rows, served = [], []
        for b, (p, t) in enumerate(part):
            full = list(p) + list(t)
            toks[b, :len(full)] = full
            for i, tok in enumerate(t):
                rows.append((b, len(p) - 1 + i))
                served.append(tok)
        rows = np.asarray(rows, np.int64)
        served = np.asarray(served, np.int64)
        blocks = ref.position_logits(params, cfg, toks, rows)
        cblocks = (ref.position_logits(params, cfg, toks, rows,
                                       precision="fp8") if control else None)
        for i, logits in blocks:
            lg = np.asarray(logits, np.float64)
            if not np.all(np.isfinite(lg)):
                raise FloatingPointError("reference logits are not finite")
            top = lg.max(axis=1)
            sd = lg.std(axis=1)
            s = served[i:i + lg.shape[0]]
            got = lg[np.arange(lg.shape[0]), s]
            worst = max(worst, float(np.max((top - got) / sd)))
            agree += int(np.sum(lg.argmax(axis=1) == s))
            n += lg.shape[0]
            if cblocks is not None:
                _, cl = next(cblocks)
                c = np.asarray(cl).argmax(axis=1)
                cgot = lg[np.arange(lg.shape[0]), c]
                worst_c = max(worst_c, float(np.max((top - cgot) / sd)))
    out = {"gap": worst, "tokens": n, "argmax_agree": agree}
    if control:
        out["control_gap"] = worst_c
    return out
