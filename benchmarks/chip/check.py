"""The comparison that decides ``correct``.

After the window, a sample of the requests (rows) it finished, drawn from
the seed, is run through the float32 reference once, teacher-forced on each
prompt and the tokens the program served. At every served position the
number compared is the gap by which the served token's reference logit lies
below the reference's best logit there; greedy decoding that agrees with the
reference reads 0. The run's reading is the widest gap over the sample.

The control puts the reference, computed in float8 (``fp8=True``), in the
program's place: its token at each position is its own argmax, read by the
same gap under the float32 reference.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import traffic


def pick_rows(done, mix: dict, n_rows: int, seed: int) -> dict:
    """{prompt_len: [(task position in ``done``, row)]}: rows drawn from the
    seed, split over the prompt lengths in the mix's proportions, with at
    least one row of the longest prompt length that finished."""
    weights = {}
    for b in mix["buckets"]:
        weights[int(b["prompt_len"])] = weights.get(int(b["prompt_len"]), 0) \
            + int(b["weight"])
    total = sum(weights.values())
    longest = max(weights)
    gen = traffic.rng(seed, traffic.SAMPLE)
    picks = {}
    for S in sorted(weights):
        cands = [(i, r) for i, d in enumerate(done) if d.task.prompt_len == S
                 for r in range(d.tokens.shape[0])]
        want = max(1 if S == longest else 0, round(n_rows * weights[S] / total))
        take = min(want, len(cands))
        if take:
            idx = gen.choice(len(cands), size=take, replace=False)
            picks[S] = [cands[j] for j in sorted(idx)]
    return picks


@jax.jit
def _gap(z, tokens):
    """z: (R, n, V) reference logits; tokens: (R, n). Best minus chosen."""
    chosen = jnp.take_along_axis(z, tokens[..., None], axis=-1)[..., 0]
    return jnp.max(z, axis=-1) - chosen


def _rows(done, rows):
    prompts = np.stack([done[i].task.prompts[r] for i, r in rows])
    served = np.stack([done[i].tokens[r] for i, r in rows]).astype(np.int32)
    return np.concatenate([prompts, served[:, :-1]], axis=1), served


def served_gaps(ref, weights, spec, done, picks) -> np.ndarray:
    """Gaps of every served token of the picked rows, flattened."""
    out = []
    for S, rows in picks.items():
        seq, served = _rows(done, rows)
        z = ref.logits(weights, spec, seq, S - 1)
        out.append(np.asarray(_gap(z, jnp.asarray(served))).ravel())
    return np.concatenate(out) if out else np.zeros(0)


def control_gaps(ref, weights, spec, done, picks) -> np.ndarray:
    """Gaps of the float8 reference's own tokens at the same positions."""
    out = []
    for S, rows in picks.items():
        seq, _ = _rows(done, rows)
        z = ref.logits(weights, spec, seq, S - 1)
        ctl = jnp.argmax(ref.logits(weights, spec, seq, S - 1, fp8=True),
                         axis=-1).astype(jnp.int32)
        out.append(np.asarray(_gap(z, ctl)).ravel())
    return np.concatenate(out) if out else np.zeros(0)
