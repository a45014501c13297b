"""The comparison that decides ``correct``.

Served tokens are greedy, so each must be the token the plain float32
reference puts first, up to rounding: the number compared is the widest
gap, over a sample of the window's finished invocations, by which a
served token's reference logit lies below the reference's best logit at
that position. The sample is drawn from the seed once the window has
closed; it holds one invocation of every function and, first, those
served right after an upload, so that weights left over from another
function fail it.

``execute`` returns the tokens decoded after the prefill's greedy token,
not that token itself, so each row's first token is taken as the one of
the reference's ``first_token_candidates`` best at the prompt's last
position (from the configuration file: more where the configuration's
own rounding moves logits further) that leaves the row's widest gap
smallest; its own gap counts too.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import numpy as np

from harness import traffic as traffic_mod

GROUP = 4              # invocations per reference call, a fixed shape


@dataclass
class Served:
    fn: int             # function index
    weight_seed: int
    request_seed: int
    tokens: np.ndarray  # (batch, new_tokens) as served
    start_type: str


def sample(served: List[Served], n: int, seed: int,
           functions: Optional[int] = None) -> List[Served]:
    """``n`` of ``served`` drawn from ``seed``: one of each function, then
    those that ran right after an upload, then any. With ``functions``,
    only the invocations of that many functions, drawn from the seed,
    count: the reference's time goes by function, as each needs its own
    weights and call."""
    rng = traffic_mod.rng_for(seed, stream=1)
    order = list(rng.permutation(len(served)))
    if functions is not None:
        fns = rng.permutation(sorted({s.fn for s in served}))
        keep = set(fns[:functions].tolist())
        order = [i for i in order if served[i].fn in keep]
    picked: List[int] = []
    seen = set()
    for i in order:
        if served[i].fn not in seen:
            seen.add(served[i].fn)
            picked.append(i)
    uploads = [i for i in order if served[i].start_type != "warm"
               and i not in picked]
    rest = [i for i in order if i not in picked and i not in uploads]
    return [served[i] for i in (picked + uploads[:n // 2] + rest)[:n]]


class Reference:
    """The configuration's plain reference, its weights made from the
    same seeds by its own code, run in fixed-shape groups."""

    def __init__(self, config: dict, batch: int, prompt: int,
                 new_tokens: int):
        import jax
        import reference
        self.ref = reference
        self.fam = reference.family(config["reference"])
        self.config, self.B, self.S, self.N = config, batch, prompt, new_tokens
        c = config
        fwd = self.fam.forward
        first = np.array([prompt - 1])
        scored = np.arange(prompt - 1, prompt + new_tokens)
        self._first = jax.jit(lambda p, t: fwd(c, p, t, first))
        self._scored = jax.jit(lambda p, t: fwd(c, p, t, scored))
        # the control: the same reference one precision step below the
        # configuration's (fp8 under bfloat16, bfloat16 under float32)
        quant = "fp8" if c["torch_dtype"] == "bfloat16" else "bfloat16"
        self._control = jax.jit(lambda p, t: fwd(c, p, t, scored, quant))

    def weights(self, weight_seed: int):
        import jax.numpy as jnp
        return self.ref.init_params(self.fam.param_table(self.config),
                                    jnp.dtype(self.config["torch_dtype"]),
                                    weight_seed)

    def gaps(self, params, group: Sequence[Served], control: bool = False):
        """Per invocation: the widest gap of its served tokens, and with
        ``control`` also that of the tokens the control precision puts
        first at the same positions."""
        import jax.numpy as jnp
        B, S, N = self.B, self.S, self.N
        k = self.config["first_token_candidates"]
        group = list(group) + [group[-1]] * (GROUP - len(group))
        vocab = self.config["vocab_size"]
        prompts = jnp.concatenate([self.ref.prompt_tokens(
            B, S, vocab, g.request_seed) for g in group])     # (G*B, S)
        first = np.asarray(self._first(params, prompts))[:, 0]  # (G*B, V)
        cand = np.argsort(-first, axis=-1)[:, :k]                # (G*B, k)
        served = np.concatenate([g.tokens for g in group])       # (G*B, N)
        rows = len(served)
        seq = np.concatenate([
            np.repeat(np.asarray(prompts), k, 0),
            cand.reshape(-1, 1),
            np.repeat(served[:, :N - 1], k, 0)], axis=1)         # (G*B*k, S+N)
        logits = np.asarray(self._scored(params, jnp.asarray(seq)))
        # tokens scored at positions S-1 .. S+N-1
        toks = np.concatenate([cand.reshape(-1, 1),
                               np.repeat(served, k, 0)], axis=1)
        per_pos = self._gap(logits, toks).reshape(rows, k, N + 1)
        gap = per_pos.max(-1)
        best = gap.argmin(-1)                                    # per row
        row_gap = gap[np.arange(rows), best]
        out = {"served": row_gap.reshape(GROUP, B).max(-1)}
        # where each invocation's widest gap lies: the first token's rank
        # among the candidates, and the position (0: the first token)
        worst = row_gap.reshape(GROUP, B).argmax(-1) + np.arange(GROUP) * B
        out["first_rank"] = best[worst]
        out["position"] = per_pos[worst, best[worst]].argmax(-1)
        if control:
            pick = np.arange(rows) * k + best
            ctl = np.asarray(self._control(params, jnp.asarray(seq[pick])))
            out["control"] = self._gap(logits[pick], ctl.argmax(-1)
                                       ).max(-1).reshape(GROUP, B).max(-1)
        return out

    @staticmethod
    def _gap(logits, toks):
        """Best logit minus the logit of ``toks`` at each position."""
        got = np.take_along_axis(logits, toks[..., None], -1)[..., 0]
        return logits.max(-1) - got


def widest_gaps(ref: Reference, picked: List[Served], control: bool = False
                ) -> Dict[str, List[float]]:
    """Per sampled invocation, grouped by function so that each
    function's reference weights are made once."""
    out: Dict[str, List[float]] = {"served": [], "first_rank": [],
                                   "position": []}
    if control:
        out["control"] = []
    by_fn: Dict[int, List[Served]] = {}
    for s in picked:
        by_fn.setdefault(s.weight_seed, []).append(s)
    for seed, items in sorted(by_fn.items()):
        params = ref.weights(seed)
        for i in range(0, len(items), GROUP):
            grp = items[i:i + GROUP]
            g = ref.gaps(params, grp, control)
            for key in out:
                out[key].extend(float(x) for x in g[key][:len(grp)])
        del params
    return out
