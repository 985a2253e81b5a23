"""The program under test, as the benchmark drives it.

Builds the program's model configuration and weights from a configuration
file, builds ``ContinuousServeEngine`` at its defaults with only the
deployment's capacity (slots, max_len, pages), warms its shapes, and runs
the closed loop through ``submit`` and ``step``, stamping every emitted
token on the host clock.  Host spans (``bench.step``, ``bench.poll``,
``bench.submit``) go into the profiler's trace when one is recorded.
"""
from __future__ import annotations

import dataclasses
import sys
import time
from pathlib import Path

import jax
import numpy as np

# published key -> program ModelConfig field
CONFIG_FIELDS = {
    "num_hidden_layers": "layers",
    "hidden_size": "d_model",
    "num_attention_heads": "heads",
    "num_key_value_heads": "kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab",
    "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
    "qk_norm": "qk_norm",
    "attention_bias": "attn_bias",
}
# what the reference computes; a program config outside it is refused
DENSE_FIELDS = {
    "family": "dense", "norm": "rms", "act": "silu", "glu": True, "pos_kind": "rope",
    "attention_pattern": ("full",), "attn_logit_cap": None, "final_logit_cap": None,
    "post_norms": False, "embed_scale": False, "n_experts": 0, "ssm_state": 0,
    "dtype": "bfloat16", "kv_cache_dtype": "bfloat16",
}


def import_program(root: Path) -> None:
    """Put the checkout's ``src/`` first on the path: the benchmark runs the
    program that sits beside it and no other copy."""
    src = Path(root) / "src"
    if not (src / "repro" / "serve" / "engine.py").is_file():
        raise SystemExit(f"bench: no program beside the benchmark (missing {src / 'repro'})")
    sys.path.insert(0, str(src))


def model_config(cfg_file: dict):
    """The program's ``ModelConfig`` for a configuration file: the program's
    own config of that name with the file's numbers in place.  A number that
    differs from the program's config and is not listed in ``reduced``, or a
    program feature the reference does not compute, is refused."""
    from repro import configs

    base = configs.get_config(cfg_file["repro_config"])
    changes = {field: type(getattr(base, field))(cfg_file[key])
               for key, field in CONFIG_FIELDS.items() if key in cfg_file}
    mc = dataclasses.replace(base, **changes)
    unlisted = [key for key, field in CONFIG_FIELDS.items()
                if key in cfg_file and getattr(base, field) != getattr(mc, field) and key not in cfg_file["reduced"]]
    if unlisted:
        raise SystemExit(f"bench: {cfg_file['name']}: keys {unlisted} differ from the program's "
                         f"{cfg_file['repro_config']!r} config and are not listed in 'reduced'")
    odd = {f: getattr(mc, f) for f, want in DENSE_FIELDS.items() if getattr(mc, f) != want}
    if odd or mc.sparsity.mode != "none" or float(cfg_file["rms_norm_eps"]) != 1e-6:
        raise SystemExit(f"bench: {cfg_file['name']}: the dense reference does not compute {odd}")
    return mc


def vocab_rows(mc) -> int:
    """Rows of the embedding the program holds (its vocab padding)."""
    from repro.models import zoo

    return int(zoo.abstract_params(mc)["embed"].shape[0])


def program_params(w: dict, mc) -> dict:
    """The reference's weights in the program's parameter tree.  The
    program's RMSNorm scales by ``1 + scale``."""
    from repro.models import zoo

    def norm(x):
        return {"scale": x - 1.0}

    block = {
        "ln1": norm(w["attn_norm"]), "ln2": norm(w["mlp_norm"]),
        "wq": w["wq"], "wk": w["wk"], "wv": w["wv"], "wo": w["wo"],
        "mlp": {"w_gate": w["w_gate"], "w_up": w["w_up"], "w_down": w["w_down"]},
    }
    if mc.qk_norm:
        block["q_norm"], block["k_norm"] = norm(w["q_norm"]), norm(w["k_norm"])
    params = {"embed": w["embed"], "final_norm": norm(w["final_norm"]), "blocks": {"0": block}}
    if not mc.tie_embeddings:
        params["lm_head"] = w["lm_head"]
    want = zoo.abstract_params(mc)
    got = jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    if jax.tree_util.tree_structure(got) != jax.tree_util.tree_structure(want) or any(
        (a.shape, a.dtype) != (b.shape, b.dtype)
        for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want))
    ):
        raise SystemExit("bench: the weights do not match the program's parameter tree")
    return params


def build_engine(mc, params, deployment: dict):
    """``ContinuousServeEngine`` at its defaults for every datapath knob;
    only the deployment's capacity is set."""
    from repro.serve.engine import ContinuousServeConfig, ContinuousServeEngine

    scfg = ContinuousServeConfig(slots=int(deployment["slots"]), max_len=int(deployment["max_len"]),
                                 num_pages=int(deployment["num_pages"]))
    return ContinuousServeEngine(mc, params, scfg)


def sampling(req):
    from repro.serve.sampling import SamplingParams

    if req.greedy:
        return SamplingParams(max_new_tokens=req.max_new)
    return SamplingParams(temperature=req.temperature, top_p=req.top_p, seed=req.seed,
                          max_new_tokens=req.max_new)


def warm_up(engine, vocab: int, seed: int) -> None:
    """Compile the engine's step programs for greedy-only and for sampled
    batches (prefill and decode each), at the cell's own engine shapes."""
    from repro.serve.sampling import SamplingParams

    rng = np.random.default_rng(int(seed) + 1)
    for sp in (SamplingParams(max_new_tokens=2),
               SamplingParams(temperature=0.8, top_p=0.95, seed=1, max_new_tokens=2)):
        engine.submit(rng.integers(1, vocab, size=8).tolist(), sampling=sp)
        engine.run_until_complete()


def prefill_prompts(engine, prompts) -> None:
    """Prefill prompts into the prefix cache (traffic set-up): shared
    documents, or the prompts that fill the pool."""
    from repro.serve.sampling import SamplingParams

    for prompt in prompts:
        engine.submit(list(prompt), sampling=SamplingParams(max_new_tokens=1))
    engine.run_until_complete()


def release(engine) -> None:
    """Drop the engine's device state (weights, pools, slot state), so the
    reference that follows the window has the chip's memory; request
    handles may still point at the engine."""
    engine.params = engine.pools = engine.slot_state = engine.occupancy = None
    engine._draft = None


@dataclasses.dataclass
class Record:
    """One request sent by a client, with the host time of each token."""

    req: object  # the engine's request handle
    sent: object  # traffic.Req
    client: int
    send_t: float
    times: list = dataclasses.field(default_factory=list)
    done_t: float | None = None
    pos: int = 0  # prefill position last seen
    slot: int | None = None  # engine slot it was served in

    @property
    def first_t(self):
        return self.times[0] if self.times else None


@dataclasses.dataclass
class Tick:
    """What one engine step did, as the request handles show it."""

    t0: float
    t1: float
    kind: str  # "decode", "prefill" or "idle"
    decode_rows: int = 0
    substeps: int = 0
    kv_lens: list = dataclasses.field(default_factory=list)  # one per decoded token
    prefill_spans: list = dataclasses.field(default_factory=list)  # (start, end) positions computed
    first_tokens: int = 0


class ClosedLoop:
    """Clients that each send their next request when the last one ends.
    Client ``j`` sends its first request at tick ``j * stagger_ticks``."""

    def __init__(self, engine, traffic, clock=time.perf_counter):
        self.engine = engine
        self.traffic = traffic
        self.clock = clock
        self.busy = [None] * traffic.clients
        self.started = [False] * traffic.clients
        self.tick_no = 0
        self.sending = True
        self.records: list[Record] = []
        self.active: list[Record] = []
        self.ticks: list[Tick] = []

    def _submit_due(self) -> None:
        for j, rec in enumerate(self.busy):
            if rec is None and self.tick_no >= j * self.traffic.stagger_ticks:
                sent = self.traffic.next(first_of=None if self.started[j] else j)
                self.started[j] = True
                t = self.clock()
                req = self.engine.submit(list(sent.prompt), sampling=sampling(sent))
                rec = Record(req=req, sent=sent, client=j, send_t=t)
                self.busy[j] = rec
                self.records.append(rec)
                self.active.append(rec)

    def _poll(self, t0: float, t1: float) -> Tick:
        tick = Tick(t0=t0, t1=t1, kind="idle")
        still = []
        for rec in self.active:
            req = rec.req
            seen = len(rec.times)
            new = len(req.generated) - seen
            shared = req.shared_tokens
            pos = req.prefill_pos
            if pos > rec.pos and pos > shared:
                tick.prefill_spans.append((max(rec.pos, shared), pos))
            rec.pos = pos
            if req.slot is not None:
                rec.slot = req.slot
            if new > 0:
                rec.times.extend([t1] * new)
                if seen == 0:
                    tick.first_tokens += 1
                else:
                    tick.decode_rows += 1
                    tick.substeps = max(tick.substeps, new)
                    tick.kv_lens.extend(len(req.prompt) + seen + i for i in range(new))
            if req.done:
                rec.done_t = t1
                self.busy[rec.client] = None
            else:
                still.append(rec)
        self.active = still
        tick.kind = "decode" if tick.decode_rows else ("prefill" if tick.prefill_spans else "idle")
        return tick

    def tick(self) -> Tick:
        """One engine step, then the stamps, then any sends that are due."""
        if self.sending:
            with jax.profiler.TraceAnnotation("bench.submit"):
                self._submit_due()
        t0 = self.clock()
        with jax.profiler.TraceAnnotation("bench.step"):
            self.engine.step()
        t1 = self.clock()
        with jax.profiler.TraceAnnotation("bench.poll"):
            tick = self._poll(t0, t1)
        self.ticks.append(tick)
        self.tick_no += 1
        return tick

    def run_for(self, seconds: float) -> tuple[float, float]:
        """Step until ``seconds`` have passed; returns the window's bounds."""
        t0 = self.clock()
        while self.clock() - t0 < seconds:
            self.tick()
        return t0, self.clock()

    def drain(self, window: tuple[float, float], limit_s: float) -> None:
        """Stop sending, and step until every request sent in ``window`` has
        its first token, or ``limit_s`` past the close."""
        self.sending = False
        t0, t1 = window
        waiting = [r for r in self.records if t0 <= r.send_t < t1]
        while any(r.first_t is None for r in waiting) and self.clock() - t1 < limit_s:
            self.tick()
