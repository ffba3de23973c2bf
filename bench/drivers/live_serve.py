"""Driver for the live serving path: the real ``BatchServer`` recorded
under simulated time through ``record_live_serve``.

The configuration file holds the model's published keys; the traffic
file the static wave (batch, prompt length, decode steps), the requests
per call and the width of their arrival burst.  The benchmark makes the
weights on the device from the seed, in one jitted call, in bfloat16 as
they are served, and hands the same arrays to the program and, after the
window, to the plain float32 reference (``bench/reference/qwen3.py``).
Prompts are uniform token ids drawn from the seed.

A call is one ``record_live_serve`` run over one burst of requests; the
ledger it returns holds the measured span of every prefill and decode
step, which is what becomes simulated time.  ``live_decode_step_p95_ms``
is the 95th percentile of all the window's decode spans.

Correctness: a sample of served requests, drawn from the seed, is run
through the reference once each over its prompt and served tokens; the
compared number is the widest gap by which a served (greedy) token's
reference logit lies below the reference's best at that position.
"""
from __future__ import annotations

import contextlib
import gc
import math
import time
from typing import Dict, List

import numpy as np

from reference import qwen3

TASK = "serve.live"


def model_config(cfg: Dict):
    """The program's ModelConfig for the published keys in ``cfg``."""
    import jax.numpy as jnp

    from repro.models.common import ModelConfig
    return ModelConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        head_dim=cfg["head_dim"], rope_theta=float(cfg["rope_theta"]),
        qk_norm=True, norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=cfg["tie_word_embeddings"], dtype=jnp.bfloat16,
        remat=False)


def weight_shapes(cfg: Dict) -> Dict[str, tuple]:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    n, v = cfg["num_hidden_layers"], cfg["vocab_size"]
    shapes = {"embed": (v, d), "attn_norm": (n, d), "wq": (n, d, h, hd),
              "wk": (n, d, kv, hd), "wv": (n, d, kv, hd),
              "wo": (n, h, hd, d), "q_norm": (n, hd), "k_norm": (n, hd),
              "mlp_norm": (n, d), "w_gate": (n, d, f), "w_up": (n, d, f),
              "w_down": (n, f, d), "final_norm": (d,)}
    if not cfg["tie_word_embeddings"]:
        shapes["lm_head"] = (d, v)
    return shapes


def prng_key(seed: int):
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def make_weights(cfg: Dict, seed: int):
    """All weights in one jitted call on the device, bfloat16: matrices
    and embeddings N(0, initializer_range) as the published initializer
    draws them, norm gain offsets N(0, 0.1) (gain = 1 + offset) so that a
    gain applied wrongly shows."""
    import jax
    import jax.numpy as jnp
    shapes = weight_shapes(cfg)
    std = float(cfg["initializer_range"])

    @jax.jit
    def gen(key):
        out = {}
        for k, (name, shape) in zip(jax.random.split(key, len(shapes)),
                                    sorted(shapes.items())):
            s = 0.1 if name.endswith("norm") else std
            out[name] = jax.random.normal(k, shape, jnp.bfloat16) * s
        return out
    return gen(prng_key(seed))


def program_params(w: Dict) -> Dict:
    """The same arrays under the served model's parameter names.  The
    served model holds its output projection as a matrix of its own;
    with tied embeddings it gets the embedding's transpose."""
    head = w["lm_head"] if "lm_head" in w else w["embed"].T
    return {"embed": w["embed"], "final_norm": w["final_norm"],
            "lm_head": head,
            "layers": {"ln1": w["attn_norm"], "ln2": w["mlp_norm"],
                       "wq": w["wq"], "wk": w["wk"], "wv": w["wv"],
                       "wo": w["wo"], "q_norm": w["q_norm"],
                       "k_norm": w["k_norm"],
                       "mlp": {"w_gate": w["w_gate"], "w_up": w["w_up"],
                               "w_down": w["w_down"]}}}


def nearest_rank(values, q: float) -> float:
    xs = sorted(values)
    return xs[max(0, math.ceil(q * len(xs)) - 1)]


class Driver:
    def __init__(self, config: Dict, traffic: Dict, seed: int, *,
                 out_dir, control: bool = False):
        self.cfg = config
        self.traffic = traffic
        self.seed = seed
        self.rng = np.random.default_rng(seed)
        self.out_dir = out_dir
        self.batch = traffic["batch"]
        self.prompt_len = traffic["prompt_len"]
        self.decode_steps = traffic["decode_steps"]
        self.weights = None
        self.stack = None
        self.calls: List[Dict] = []     # per call: decode costs (ns)
        self.prompts: List[np.ndarray] = []     # per served wave
        self._queue: List[tuple] = []
        self.failures: List[tuple] = []  # (requests, error) per failed call

    # -- set-up ----------------------------------------------------------------
    def setup(self) -> None:
        import jax

        from repro.sim.live import ServeStack
        self.weights = make_weights(self.cfg, self.seed)
        jax.block_until_ready(self.weights)
        driver = self

        class Stack(ServeStack):
            """The program's serve stack with the benchmark's weights
            and seeded prompts; keeps every served token (on the
            device) for the check."""

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
                super().prefill(wave, batch)
                self.served.append([self._tok])

            def decode(self, wave, d):
                super().decode(wave, d)
                self.served[-1].append(self._tok)

        self.stack = Stack(cfg=model_config(self.cfg),
                           max_batch=self.batch,
                           prompt_len=self.prompt_len,
                           decode_steps=self.decode_steps, seed=self.seed)
        self.stack.setup()

    # -- the timed call --------------------------------------------------------
    def call(self) -> None:
        import jax.numpy as jnp

        from repro.sim.live import record_live_serve
        n = self.traffic["requests_per_call"]
        while len(self._queue) < n:       # at most one wave per request
            host = self.rng.integers(0, self.cfg["vocab_size"],
                                     (self.batch, self.prompt_len),
                                     dtype=np.int64).astype(np.int32)
            self._queue.append((host, jnp.asarray(host)))
        arrivals = np.sort(self.rng.integers(
            1, self.traffic["burst_ns"] + 1, n)).tolist()
        t0 = time.perf_counter()
        try:
            _report, ledger = record_live_serve(
                self.out_dir / "serve_trace.json", stack=self.stack,
                arrivals=arrivals, max_batch=self.batch,
                decode_steps=self.decode_steps)
        except Exception as e:        # answers that never come
            self.failures.append((n, repr(e)))
            return
        t1 = time.perf_counter()
        costs = [e["cost_ns"] for e in ledger.tasks[TASK]
                 if e["label"].startswith("decode:")]
        self.calls.append({"s": t1 - t0, "decode_ns": costs,
                           "requests": n})

    # -- numbers ---------------------------------------------------------------
    def end_to_end(self, window_s: float) -> Dict[str, float]:
        costs = [c for call in self.calls for c in call["decode_ns"]]
        return {"live_decode_step_p95_ms": nearest_rank(costs, 0.95) * 1e-6}

    def counts(self, traced: int) -> Dict:
        """Per decode step of the traced calls: its measured span and
        the least time its work needs on this chip."""
        steps = []
        for call in self.calls[:traced]:
            for i, ns in enumerate(call["decode_ns"]):
                d = i % self.decode_steps
                steps.append((self.prompt_len + d + 1, ns))
        return {"decode_steps": steps, "batch": self.batch}

    @contextlib.contextmanager
    def spans(self):
        """Host spans around each prefill and decode step."""
        import jax
        stack = self.stack
        saved = stack.prefill, stack.decode

        def wrap(fn, label):
            def inner(*args):
                with jax.profiler.TraceAnnotation(label):
                    return fn(*args)
            return inner
        stack.prefill = wrap(saved[0], "bench.prefill")
        stack.decode = wrap(saved[1], "bench.decode")
        try:
            yield
        finally:
            del stack.prefill, stack.decode

    def release(self) -> None:
        """Free the server and its cache; the weights stay for the
        reference, which the benchmark made and the program only read."""
        whole = [(p, w) for p, w in zip(self.prompts, self.stack.served)
                 if len(w) == self.decode_steps + 1]     # waves that ended
        self.prompts = [p for p, _ in whole]
        self.tokens = [np.stack([np.asarray(t) for t in w], axis=1)
                       for _, w in whole]               # (batch, D + 1)
        self.stack.server = None
        self.stack._tok = self.stack._cache = None
        self.stack.served = []
        gc.collect()

    # -- correctness -----------------------------------------------------------
    def sample(self):
        """(prompt + served tokens (k, P + D), served tokens (k, D + 1))
        of ``check_requests`` served requests drawn from the seed."""
        pairs = [(w, r) for w in range(len(self.tokens))
                 for r in range(self.batch)]
        rng = np.random.default_rng([self.seed, 1])
        k = min(self.traffic["check_requests"], len(pairs))
        pick = [pairs[i] for i in sorted(rng.choice(len(pairs), k,
                                                    replace=False))]
        served = np.stack([self.tokens[w][r] for w, r in pick])
        prompts = np.stack([self.prompts[w][r] for w, r in pick])
        seq = np.concatenate([prompts, served[:, :-1]], axis=1)
        return seq, served

    def gaps(self, quant: str = "f32"):
        """Per judged position, the reference's best logit minus its
        logit of the served token; with ``quant`` the token judged is
        the one the reference at that precision puts first (the
        control's reading)."""
        import jax.numpy as jnp
        seq, served = self.sample()
        ref = qwen3.logits(self.cfg, self.weights, seq, self.prompt_len - 1)
        if quant == "f32":
            tok = jnp.asarray(served)
        else:
            low = qwen3.logits(self.cfg, self.weights, seq,
                               self.prompt_len - 1, quant=quant)
            tok = jnp.argmax(low, axis=-1)
            del low
        best = jnp.max(ref, axis=-1)
        got = jnp.take_along_axis(ref, tok[..., None], axis=-1)[..., 0]
        return np.asarray(best - got, np.float64)

    def check(self):
        lost = sum(n for n, _ in self.failures)
        checks = {"widest_logit_gap": {
            "value": None,
            "limit": self.traffic["limits"]["widest_logit_gap"], "of": 0},
            "requests_failed": {"value": lost, "limit": 0}}
        if self.failures:
            checks["requests_failed"]["error"] = self.failures[0][1]
        if self.tokens:
            g = self.gaps()
            checks["widest_logit_gap"].update(value=float(g.max()),
                                              of=int(g.size))
        served = sum(c["requests"] for c in self.calls)
        return checks, served + lost, lost
