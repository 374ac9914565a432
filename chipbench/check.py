"""The comparison that decides `correct`.

Each number is a gap between what the timed path produced and what
`reference.py` computes from the same inputs; lower is better, and the
run is correct when every number the cell has is present and at most
its limit (the workload file's `limits`):

train_loss_gap    largest relative gap of the first three training
                  steps' losses of the first job, which starts from the
                  benchmark's weights;
train_grad_gap    the first clipped gradient's leaf norms, worked out
                  from the AdamW first moment after one step;
train_update_gap  the leaf norms of the parameters' change after three
                  steps. Both take the worst leaf: |program - reference|
                  over the larger of the reference's norm of that leaf
                  and its median leaf norm, over the leaves whose
                  reference gradient norm is at least a thousandth of
                  the median leaf's;
eval_acc_gap      largest |accuracy - reference accuracy| over the last
                  timed window's metrics eval (every grouped camera);
serve_logit_gap   largest gap by which a token served in the last timed
                  window lies below the reference's best logit at its
                  position (prompt and served tokens run once through
                  the reference);
drift_score_gap   largest |score - reference score| over every camera
                  in the last timed window, the reference histogramming
                  the same tokens and scoring in float64; missing when
                  the window scored only part of the fleet.

Where no step trained, eval and serve are scored with the benchmark's
own weights, made again from the seed: every job and snapshot should
still hold them. After retraining they are scored with the weights the
program holds, since no reference follows every step of it.

`readings` gives the same numbers with a control or a fault in the
program's place; the benchmark's own runs never compute them.
"""
from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from chipbench import reference

LEAF_FLOOR = 1e-3     # leaves below this share of the median gradient


def leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
             keep) -> float:
    med = float(np.median([ref[k] for k in keep]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep)


def kept_leaves(ref_grad: Dict[str, float]):
    med = float(np.median(list(ref_grad.values())))
    return [k for k, v in ref_grad.items() if v >= LEAF_FLOOR * med]


def train_numbers(got: dict, ref: dict) -> Dict[str, float]:
    keep = kept_leaves(ref["grad"])
    return {
        "train_loss_gap": max(abs(a - b) / abs(b)
                              for a, b in zip(got["loss"], ref["loss"])),
        "train_grad_gap": leaf_gap(got["grad"], ref["grad"], keep),
        "train_update_gap": leaf_gap(got["update"], ref["update"], keep),
    }


def eval_gap(accs, ref_accs) -> float:
    return float(np.max(np.abs(np.asarray(accs) - np.asarray(ref_accs))))


def serve_gaps(arch, model: dict, params, requests, prompt_len: int,
               lowp: Optional[str] = None, alter: bool = False) -> float:
    """Widest gap of a served token below the reference's best logit.
    With `lowp`, the tokens scored are those the low-precision forward
    puts first at each position; with `alter`, each served token is
    replaced by its successor in the vocabulary."""
    toks = np.stack([np.concatenate([p, s[:-1]]) for p, s in requests])
    served = np.stack([s for _, s in requests])
    lg = np.asarray(reference.logits(arch, model, params, toks))
    pos = prompt_len - 1 + np.arange(served.shape[1])
    ref = lg[:, pos]                                   # (R, new, V)
    if lowp is not None:
        low = np.asarray(reference.logits(arch, model, params, toks, lowp))
        served = low[:, pos].argmax(-1)
    elif alter:
        served = (served + 1) % model["vocab_size"]
    picked = np.take_along_axis(ref, served[..., None], -1)[..., 0]
    return float(np.max(ref.max(-1) - picked))


def compare(arch, model: dict, opt: dict, rec: dict, params0, serve_params,
            eval_params, prompt_len: int, buckets: int,
            mode: Optional[str] = None) -> Dict[str, float]:
    """The cell's numbers, through the architecture module `arch`
    (`chipbench/arch/`). `rec` holds what the timed path produced
    (see bench.Probe.record); mode None compares the program, "control"
    puts the reference at the next lower precision in its place,
    "half_batch" / "altered" put the reference with that fault there,
    and "bf16" puts a training reference rounded as the program's
    bfloat16 step rounds (a witness of how far the program's own
    precision accounts for its gaps)."""
    out: Dict[str, float] = {}
    tr = rec.get("train")
    if tr and len(tr["batches"]) == 3:
        ref = reference.train(arch, model, opt, params0, tr["batches"])
        if mode is None:
            got = tr
        elif mode == "control":
            got = reference.train(arch, model, opt, params0, tr["batches"],
                                  lowp="fp8")
        elif mode == "bf16":
            got = reference.train(arch, model, opt, params0, tr["batches"],
                                  lowp="bf16")
        elif mode == "half_batch":
            got = reference.train(arch, model, opt, params0,
                                  [b[: len(b) // 2] for b in tr["batches"]])
        else:
            got = None
        if got is not None:
            out.update(train_numbers(got, ref))
    ev = rec.get("eval")
    if ev:
        ref_acc, got_acc = [], []
        for jid, toks, acc in ev:
            r = reference.accuracy(arch, model, eval_params[jid],
                                   toks[None])[0]
            ref_acc.append(r)
            if mode == "control":
                got_acc.append(reference.accuracy(
                    arch, model, eval_params[jid], toks[None], "bf16")[0])
            elif mode == "altered":
                # labels off by one position: the prediction for token i
                # scored against token i itself
                lg = np.asarray(reference.logits(
                    arch, model, eval_params[jid], toks))
                got_acc.append(float(np.mean(lg[:, :-1].argmax(-1)
                                             == toks[:, :-1])))
            else:
                got_acc.append(acc)
        if mode in (None, "control", "altered"):
            out["eval_acc_gap"] = eval_gap(got_acc, ref_acc)
    sv = rec.get("serve")
    if sv and mode in (None, "control", "altered"):
        by_group: Dict[str, list] = {}
        for gid, prompt, served in sv:
            by_group.setdefault(gid, []).append((prompt, served))
        out["serve_logit_gap"] = max(
            serve_gaps(arch, model, serve_params[g], reqs, prompt_len,
                       lowp="fp8" if mode == "control" else None,
                       alter=mode == "altered")
            for g, reqs in by_group.items())
    dr = rec.get("drift")
    if dr and mode in (None, "control"):
        vocab = model["vocab_size"]
        href = reference.histograms(dr["ref_tokens"], buckets, vocab)
        hlive = reference.histograms(dr["tokens"], buckets, vocab)
        want = reference.js(hlive, href)
        got = (dr["scores"] if mode is None
               else reference.js(hlive, href, dtype="bf16"))
        out["drift_score_gap"] = float(np.max(np.abs(np.asarray(got)
                                                     - want)))
    return out


def judge(numbers: Dict[str, float], limits: Dict[str, float]):
    """(correct, {name: {"value", "limit"}}): every limited number must
    be present and at most its limit."""
    checks = {}
    ok = True
    for name, limit in limits.items():
        v = numbers.get(name)
        checks[name] = {"value": v, "limit": limit}
        if v is None or not np.isfinite(v) or v > limit:
            ok = False
    return ok, checks
