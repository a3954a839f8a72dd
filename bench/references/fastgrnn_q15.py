"""Plain reference of the deployed Q15 FastGRNN classifier (NumPy, float32).

It follows the configuration file and the paper, and nothing of the program:

* weights: the configuration's init distribution, drawn on the device in
  one jitted call from the seed (:func:`make_params`); the program and this
  reference are both handed these float weights;
* compression: hard-thresholding to the stated sparsity (keep the largest
  magnitudes of each factor), then per-tensor symmetric PTQ at the stated
  bits: ``scale = max|W| / qmax``, ``Wq = clip(round(W / scale))``, and the
  kernel uses ``float32(Wq) * scale``;
* the cell, paper Eq. (1)-(3), with the deployed engine's fixed op order:
  every matvec accumulates ``acc + x_j * A[:, j]`` over ascending j in
  float32, and sigmoid/tanh are 256-entry bucket-centre tables over
  [-8, 8], read at the nearest bucket and saturated outside;
* tumbling windows: h starts at zero, steps once per sample, and after the
  last sample of a window the head gives the logits.

The configuration states a bit-exact guarantee, so this order is part of
the semantics, and a correct program gives the same bits.
"""
from __future__ import annotations

import numpy as np

LOW_RANK = ("W1", "W2", "U1", "U2")


def make_params(cfg: dict, seed_seq: np.random.SeedSequence) -> dict:
    """Float32 weights of the configuration's init, made on the default
    device in one jitted call from the seed, returned as host arrays."""
    import jax
    import jax.numpy as jnp

    m, init = cfg["model"], cfg["init"]
    d, H, C = m["input_dim"], m["hidden_dim"], m["num_classes"]
    shapes = ({"W1": (H, m["rank_w"]), "W2": (d, m["rank_w"]),
               "U1": (H, m["rank_u"]), "U2": (H, m["rank_u"])}
              if m["rank_w"] else {"W": (H, d), "U": (H, H)})
    shapes["head_w"] = (H, C)

    @jax.jit
    def draw(key):
        ks = jax.random.split(key, len(shapes))
        out = {n: init["weight_std"] * jax.random.normal(k, s, jnp.float32)
               for k, (n, s) in zip(ks, sorted(shapes.items()))}
        out.update(b_z=jnp.full((H,), init["b_z"], jnp.float32),
                   b_h=jnp.full((H,), init["b_h"], jnp.float32),
                   zeta=jnp.asarray(init["zeta_raw"], jnp.float32),
                   nu=jnp.asarray(init["nu_raw"], jnp.float32),
                   head_b=jnp.full((C,), init["head_b"], jnp.float32))
        return out

    words = seed_seq.generate_state(2, np.uint32)
    key = jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")
    return {n: np.asarray(v) for n, v in jax.device_get(draw(key)).items()}


def _keep_largest(w: np.ndarray, keep: int) -> np.ndarray:
    """Zero all but the ``keep`` largest-magnitude entries (ties go to the
    earlier entry)."""
    flat = np.abs(w).reshape(-1)
    order = np.argsort(-flat, kind="stable")
    mask = np.zeros(flat.size, bool)
    mask[order[:keep]] = True
    return np.where(mask.reshape(w.shape), w, np.float32(0)).astype(np.float32)


def _quantize(w: np.ndarray, bits: int) -> np.ndarray:
    """Per-tensor symmetric PTQ; returns the dequantized float32 weights."""
    qmax = np.float32((1 << bits) - 1)
    amax = np.float32(np.max(np.abs(w)))
    scale = amax / qmax if amax > 0 else np.float32(1) / qmax
    q = np.clip(np.round(w / scale), -qmax - 1, qmax)
    return (q.astype(np.float32) * scale).astype(np.float32)


def _table(fn, comp: dict) -> np.ndarray:
    lo, hi, n = comp["lut_min"], comp["lut_max"], comp["lut_size"]
    centers = lo + (np.arange(n) + 0.5) * ((hi - lo) / n)
    return fn(centers).astype(np.float32)


class Reference:
    """The compressed cell of one configuration, built from float weights."""

    def __init__(self, cfg: dict, params: dict):
        comp = cfg["compression"]
        self.low_rank = bool(cfg["model"]["rank_w"])
        w = {}
        for n, v in params.items():
            v = np.asarray(v, np.float32)
            if n in comp["iht_leaves"] and v.size > 1:
                v = _keep_largest(v, int(round(v.size * (1 - comp["iht_sparsity"]))))
            if n not in comp["float_leaves"] and v.ndim:
                v = _quantize(v, comp["weight_bits"])
            w[n] = v
        self.w = w
        sig = lambda r: np.float32(1.0 / (1.0 + np.exp(-float(r))))
        self.zeta, self.nu = sig(w["zeta"]), sig(w["nu"])
        self.sig = _table(lambda x: 1.0 / (1.0 + np.exp(-x)), comp)
        self.tanh = _table(np.tanh, comp)
        self.lo, self.hi = float(comp["lut_min"]), float(comp["lut_max"])
        self.inv_bw = comp["lut_size"] / (self.hi - self.lo)

    @staticmethod
    def _matvec(A: np.ndarray, x: np.ndarray) -> np.ndarray:
        """out[b, i] = sum_j A[i, j] x[b, j], accumulated over ascending j."""
        out = np.zeros((x.shape[0], A.shape[0]), np.float32)
        for j in range(A.shape[1]):
            out = out + x[:, j:j + 1] * A[:, j][None, :]
        return out

    def _lut(self, table: np.ndarray, v: np.ndarray) -> np.ndarray:
        idx = np.clip(((v - self.lo) * self.inv_bw).astype(np.int32),
                      0, table.size - 1)
        y = table[idx]
        y = np.where(v >= self.hi, table[-1], y)
        return np.where(v <= self.lo, table[0], y).astype(np.float32)

    def step(self, h: np.ndarray, x: np.ndarray) -> np.ndarray:
        w, mv = self.w, self._matvec
        if self.low_rank:
            pre = mv(w["W1"], mv(w["W2"].T, x)) + mv(w["U1"], mv(w["U2"].T, h))
        else:
            pre = mv(w["W"], x) + mv(w["U"], h)
        z = self._lut(self.sig, pre + w["b_z"])
        h_tilde = self._lut(self.tanh, pre + w["b_h"])
        return ((self.zeta * (1.0 - z) + self.nu) * h_tilde + z * h).astype(np.float32)

    def logits(self, windows: list, block: int = 8192) -> np.ndarray:
        """A list of n (T, d) windows -> (n, C) logits, each window from
        h = 0."""
        out = []
        for s in range(0, len(windows), block):
            xs = np.asarray(windows[s:s + block], np.float32)
            h = np.zeros((len(xs), self.w["b_z"].shape[0]), np.float32)
            for t in range(xs.shape[1]):
                h = self.step(h, xs[:, t])
            out.append(self._matvec(self.w["head_w"].T, h) + self.w["head_b"])
        return np.concatenate(out) if out else np.zeros((0, self.w["head_b"].size),
                                                        np.float32)
