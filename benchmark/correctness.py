"""The comparison that decides ``correct``: chip_smoke.py's margin method
against the plain float32 reference.

For each probe the reference is teacher-forced over prompt + served tokens.
Served token j was chosen at position n + j - 1; its reference logit must lie
within the configuration's stated tolerance of that position's reference
maximum. Logits and not tokens are compared: with random weights the largest
logit changes on rounding.
"""

from __future__ import annotations

from benchmark import manifest


def load_reference(name: str):
    """The reference module a configuration file names, found by name."""
    return manifest.module("reference", name)


def probe_margins(params, model: dict, probes: list[dict],
                  reference: str) -> dict:
    """Worst margin over ``probes`` = [{"name", "prompt", "tokens"}].
    Every sequence is padded to one length (one compile); causal attention
    keeps the padding out of every real position."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    ref = load_reference(reference)
    seqs = [p["prompt"] + p["tokens"] for p in probes]
    t = max(len(s) for s in seqs)
    fwd = jax.jit(lambda p, x: ref.forward(p, x, model))
    worst, at, checked = 0.0, None, 0
    for p, s in zip(probes, seqs):
        tokens = jnp.asarray(s + [0] * (t - len(s)), jnp.int32)
        n, m = len(p["prompt"]), len(p["tokens"])
        rows = np.asarray(jax.device_get(fwd(params, tokens)[n - 1:n + m - 1]))
        if not np.isfinite(rows).all():
            return {"worst_margin": float("inf"), "worst_at": [p["name"], -1],
                    "tokens_checked": checked, "seq_len": t}
        margins = rows.max(axis=-1) - rows[np.arange(m), p["tokens"]]
        j = int(margins.argmax())
        checked += m
        if float(margins[j]) > worst:
            worst, at = float(margins[j]), [p["name"], j]
    return {"worst_margin": worst, "worst_at": at, "tokens_checked": checked,
            "seq_len": t}
