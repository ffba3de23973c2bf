"""Driver for the live serving path on a latent-attention expert model
(Moonlight-16B-A3B): the real ``BatchServer`` recorded under simulated
time through ``record_live_serve``, as ``live_serve`` drives the dense
decoder, with this model's weights, program configuration and
reference (``bench/reference/moonlight.py``).

The configuration file holds the published keys; ``n_routed_experts``
there is the experts this chip holds, and ``deployment`` states the
router's width and the first held expert.  The weights are made on the
device from the seed in one jitted call, in bfloat16 as they are served
(the router and its correction bias in float32), grouped as the program
stacks its layers (the leading dense layer, then the expert layers), and
the same arrays go to the program and, after the window, to the plain
float32 reference.

A call and the timed numbers are ``live_serve``'s: one
``record_live_serve`` run over one burst of requests; the 95th
percentile of the window's decode spans.  The check judges
``check_requests`` served requests, each position by the gap by which
the served token's reference logit lies below the reference's best, but
only at the positions clear of routing near-ties: where, in every expert
layer, no held expert's biased score lies within the traffic's
``tie_margin`` of the boundary between the chosen experts and the rest
(the reference's ``routing_margin``).  At a near-tie a bfloat16 server
may rightly choose another expert than the float32 reference, and the
logits then differ by as much as a real fault moves them; elsewhere the
two compute the same held experts at that position.  With
``control`` the check reads the float8 control instead: the gap of the
token the reference puts first when computed in float8
(``bench/control.py``).
"""
from __future__ import annotations

from typing import Dict, List

from drivers import live_serve
from reference import moonlight

TASK = live_serve.TASK
GAP = "widest_gap_clear_of_ties"
NORMS = ("attn_norm", "mlp_norm", "kv_norm", "final_norm")
BIAS_STD = 0.05          # the assumed draw of the correction bias


def model_config(cfg: Dict):
    """The program's ModelConfig for the published keys in ``cfg``."""
    import jax.numpy as jnp

    from repro.models.common import ModelConfig
    dep = cfg["deployment"]
    return ModelConfig(
        name=cfg["name"], family="moe",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=cfg["tie_word_embeddings"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        n_experts=dep["n_routed_experts"],
        top_k=cfg["num_experts_per_tok"],
        moe_d_ff=cfg["moe_intermediate_size"],
        n_shared_experts=cfg["n_shared_experts"],
        first_k_dense=cfg["first_k_dense_replace"],
        scoring_func=cfg["scoring_func"],
        routed_scaling=float(cfg["routed_scaling_factor"]),
        n_held_experts=cfg["n_routed_experts"],
        first_held_expert=dep["first_held_expert"],
        dtype=jnp.bfloat16, remat=False)


def weight_shapes(cfg: Dict) -> Dict[str, Dict[str, tuple]]:
    """Per group (``top``, ``dense``, ``moe``), name -> shape."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    h, nope, rope = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"])
    r, vh = cfg["kv_lora_rank"], cfg["v_head_dim"]
    k = cfg["first_k_dense_replace"]
    n = cfg["num_hidden_layers"] - k
    e, eh = cfg["deployment"]["n_routed_experts"], cfg["n_routed_experts"]
    fe, fs = (cfg["moe_intermediate_size"],
              cfg["n_shared_experts"] * cfg["moe_intermediate_size"])
    f = cfg["intermediate_size"]

    def attn(m):
        return {"attn_norm": (m, d), "mlp_norm": (m, d),
                "wq": (m, d, h, nope + rope), "wkv_a": (m, d, r + rope),
                "kv_norm": (m, r), "wkv_b": (m, r, h, nope + vh),
                "wo": (m, h, vh, d)}
    return {
        "top": {"embed": (v, d), "lm_head": (d, v), "final_norm": (d,)},
        "dense": {**attn(k), "w_gate": (k, d, f), "w_up": (k, d, f),
                  "w_down": (k, f, d)},
        "moe": {**attn(n), "router": (n, d, e), "router_bias": (n, e),
                "e_gate": (n, eh, d, fe), "e_up": (n, eh, d, fe),
                "e_down": (n, eh, fe, d), "s_gate": (n, d, fs),
                "s_up": (n, d, fs), "s_down": (n, fs, d)},
    }


def make_weights(cfg: Dict, seed: int):
    """All weights in one jitted call on the device: matrices and
    embeddings N(0, initializer_range), bfloat16, the router float32;
    norm gain offsets N(0, 0.1) (gain = 1 + offset); the correction bias
    N(0, BIAS_STD), float32.  Returns ``live_serve``'s flat layout at the
    top (``embed``, ``lm_head``, ``final_norm``) with the two stacked
    groups under ``dense`` and ``moe``."""
    import jax
    import jax.numpy as jnp
    groups = weight_shapes(cfg)
    std = float(cfg["initializer_range"])
    flat = sorted((g, name, shape) for g, names in groups.items()
                  for name, shape in names.items())

    @jax.jit
    def gen(key):
        out = {"dense": {}, "moe": {}}
        for kk, (g, name, shape) in zip(jax.random.split(key, len(flat)),
                                        flat):
            if name == "router_bias":
                w = jax.random.normal(kk, shape, jnp.float32) * BIAS_STD
            elif name == "router":
                w = jax.random.normal(kk, shape, jnp.float32) * std
            else:
                s = 0.1 if name in NORMS else std
                w = jax.random.normal(kk, shape, jnp.bfloat16) * s
            (out if g == "top" else out[g])[name] = w
        return out
    return gen(live_serve.prng_key(seed))


def program_params(w: Dict) -> Dict:
    """The same arrays under the served model's parameter names."""
    def layers(g, ffn):
        return {"ln1": g["attn_norm"], "ln2": g["mlp_norm"], "wq": g["wq"],
                "wkv_a": g["wkv_a"], "kv_norm": g["kv_norm"],
                "wkv_b": g["wkv_b"], "wo": g["wo"], **ffn}
    d, m = w["dense"], w["moe"]
    return {
        "embed": w["embed"], "final_norm": w["final_norm"],
        "lm_head": w["lm_head"],
        "dense_layers": layers(d, {"mlp": {
            "w_gate": d["w_gate"], "w_up": d["w_up"],
            "w_down": d["w_down"]}}),
        "layers": layers(m, {"moe": {
            "router": m["router"], "router_bias": m["router_bias"],
            "w_gate": m["e_gate"], "w_up": m["e_up"], "w_down": m["e_down"],
            "shared": {"w_gate": m["s_gate"], "w_up": m["s_up"],
                       "w_down": m["s_down"]}}}),
    }


class Driver(live_serve.Driver):
    def __init__(self, config: Dict, traffic: Dict, seed: int, *,
                 out_dir, control: bool = False):
        super().__init__(config, traffic, seed, out_dir=out_dir)
        self.quant = "fp8" if control else "f32"

    def setup(self) -> None:
        import jax

        from repro.sim.live import ServeStack
        served = model_config(self.cfg)
        self.weights = make_weights(self.cfg, self.seed)
        jax.block_until_ready(self.weights)
        driver = self

        class Stack(ServeStack):
            """The program's serve stack with the benchmark's weights and
            seeded prompts; keeps every served token (on the device) for
            the check, and drops a finished wave's cache before the next
            prefill, as a server frees a finished batch's cache."""

            def setup(self):
                if self.server is not None:
                    return
                import jax.numpy as jnp

                from repro.serve.loop import BatchServer
                self.server = BatchServer(
                    self.cfg, program_params(driver.weights),
                    max_new_tokens=self.decode_steps + 1)
                self.served: List[list] = []
                p = jnp.zeros((self.max_batch, self.prompt_len), jnp.int32)
                logits, cache = self.server._prefill(self.server.params,
                                                     p, None)
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                logits, _ = self.server._decode(self.server.params, tok,
                                                cache)
                jax.block_until_ready(jnp.argmax(logits, axis=-1))

            def _prompts(self, wave):
                return self._current

            def prefill(self, wave, batch):
                host, self._current = driver._queue.pop(0)
                driver.prompts.append(host)
                self._cache = None
                super().prefill(wave, batch)
                self.served.append([self._tok])

            def decode(self, wave, d):
                super().decode(wave, d)
                self.served[-1].append(self._tok)

        self.stack = Stack(cfg=served,
                           max_batch=self.batch,
                           prompt_len=self.prompt_len,
                           decode_steps=self.decode_steps, seed=self.seed)
        self.stack.setup()

    def judged(self, quant: str = None):
        """Per judged position, the reference's best logit minus its
        logit of the served token, and the least routing margin over the
        expert layers; under ``quant`` "fp8" (the control, and the
        default with ``control``) the token judged is the one the
        reference computed in float8 puts first."""
        import jax.numpy as jnp
        import numpy as np
        quant = quant or self.quant
        seq, served = self.sample()
        ref, margin = moonlight.logits(self.cfg, self.weights, seq,
                                       self.prompt_len - 1, margins=True)
        if quant == "f32":
            tok = jnp.asarray(served)
        else:
            low = moonlight.logits(self.cfg, self.weights, seq,
                                   self.prompt_len - 1, quant=quant)
            tok = jnp.argmax(low, axis=-1)
            del low
        best = jnp.max(ref, axis=-1)
        got = jnp.take_along_axis(ref, tok[..., None], axis=-1)[..., 0]
        return (np.asarray(best - got, np.float64),
                np.asarray(jnp.min(margin, axis=-1), np.float64))

    def check(self):
        """The widest gap over the positions clear of routing near-ties
        (``of``: how many), and the requests lost.  Releases the server
        first where the caller has not (``bench/control.py`` checks
        straight after the calls)."""
        if self.stack.server is not None:
            self.release()
        lost = sum(n for n, _ in self.failures)
        checks = {GAP: {"value": None,
                        "limit": self.traffic["limits"][GAP], "of": 0},
                  "requests_failed": {"value": lost, "limit": 0}}
        if self.failures:
            checks["requests_failed"]["error"] = self.failures[0][1]
        if self.tokens:
            gap, margin = self.judged()
            clear = margin >= self.traffic["tie_margin"]
            if clear.any():
                checks[GAP].update(value=float(gap[clear].max()),
                                   of=int(clear.sum()))
        served = sum(c["requests"] for c in self.calls)
        return checks, served + lost, lost
