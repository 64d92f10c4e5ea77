"""The system under test, driven as a deployment would drive it: the
program's ``ServingEngine`` built as ``launch/serve.py`` builds it (one
device, no mesh), fed through ``submit`` and ``step``, with every
output token timestamped on the host clock when it appears after the
step's sync.  This is the only module that imports the program.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from contextlib import nullcontext
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from . import traffic as traffic_mod
from . import weights as weights_mod

#: published config key -> the program's ModelConfig field
PUBLISHED_TO_PROGRAM = {
    "hidden_size": "d_model", "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff", "vocab_size": "vocab",
    "rope_theta": "rope_theta", "tie_word_embeddings": "tie_embeddings",
}


def program_config(cfg: dict):
    """The program's ModelConfig for a configuration file: its ``arch``
    with the file's ``program.replace`` applied.  Refuses one whose
    sizes differ from the published keys of the file."""
    from repro.configs import get_config
    prog = cfg["program"]
    pc = dataclasses.replace(get_config(prog["arch"]),
                             **prog.get("replace", {}))
    for key, field in PUBLISHED_TO_PROGRAM.items():
        if key in cfg and getattr(pc, field) != cfg[key]:
            raise ValueError(f"program {field}={getattr(pc, field)} but "
                             f"the configuration states {key}={cfg[key]}")
    hd = cfg.get("head_dim") or cfg["hidden_size"] // cfg[
        "num_attention_heads"]
    if pc.hd != hd:
        raise ValueError(f"program head size {pc.hd}, stated {hd}")
    return pc


def sizes(cfg: dict) -> Dict[str, int]:
    h = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // h
    return {"d": cfg["hidden_size"], "qdim": h * hd,
            "ffn": cfg["intermediate_size"]}


def weight_generator(cfg: dict, pc):
    from repro import models
    return weights_mod.generator(models.param_shapes(pc), sizes(cfg))


class TreeView:
    """The reference's view of a weight tree in the program's layout:
    float32 arrays in the reference's own layout, one layer at a time."""

    def __init__(self, tree, cfg: dict):
        self.tree = tree
        H = cfg["num_attention_heads"]
        KH = cfg.get("num_key_value_heads", H)
        hd = cfg.get("head_dim") or cfg["hidden_size"] // H
        d = cfg["hidden_size"]

        def layer(tree, i):
            g = tree["groups"]["pos0"]
            f32 = lambda a: a[i].astype(jnp.float32)        # noqa: E731

            def ln(sub):
                s = g[sub].get("scale")
                return None if s is None else 1.0 + f32(s)
            a, f = g["attn"], g["ffn"]
            return {"wq": f32(a["wq"]).reshape(d, H * hd),
                    "wk": f32(a["wk"]).reshape(d, KH * hd),
                    "wv": f32(a["wv"]).reshape(d, KH * hd),
                    "wo": f32(a["wo"]).reshape(H * hd, d),
                    "wg": f32(f["w_gate"]), "wu": f32(f["w_up"]),
                    "wd": f32(f["w_down"]),
                    "ln1": ln("norm1"), "ln2": ln("norm2")}

        self._layer = jax.jit(layer)

    def embed(self):
        return self.tree["embed"]["tok"]

    def head(self):
        lm = self.tree["embed"].get("lm_head")
        return self.tree["embed"]["tok"] if lm is None else lm.T

    def final_norm(self):
        s = self.tree.get("final_norm", {}).get("scale")
        return None if s is None else 1.0 + s.astype(jnp.float32)

    def layer(self, i: int):
        return self._layer(self.tree, jnp.int32(i))


class HostClock:
    """``perf_counter`` that remembers its first reading: the program's
    Tracer stamps events relative to the first call of its clock, so
    ``first + ts / 1e6`` puts them back on the harness's clock."""

    def __init__(self):
        self.first: Optional[float] = None

    def __call__(self) -> float:
        t = time.perf_counter()
        if self.first is None:
            self.first = t
        return t


def build_engine(pc, params, engine_cfg: dict, tracer):
    from repro.serving.engine import ServingEngine
    from repro.serving.sched import SchedConfig
    return ServingEngine(
        pc, params, dp=1, b_local=engine_cfg["b_local"],
        max_len=engine_cfg["max_len"],
        chunk_size=engine_cfg["chunk_size"], mesh=None,
        sched=SchedConfig(pin_pages=engine_cfg.get("pin_pages", 0),
                          pin_rows=engine_cfg.get("pin_rows", 4)),
        max_restarts=0, tracer=tracer)


def new_tracer():
    from repro.serving.trace import Tracer
    clock = HostClock()
    return Tracer(capacity=5_000_000, clock=clock), clock


@dataclasses.dataclass
class StepRec:
    a: float                          # host clock at step entry
    b: float                          # ... at return (after the sync)
    lanes: List[Tuple[int, int]]      # (tokens fed, context after)
    emitted: int
    dispatched: bool = True           # False: the idle fast path

    @property
    def width1(self) -> bool:
        return bool(self.lanes) and all(n == 1 for n, _ in self.lanes)


class LoadGen:
    """Submits planned requests to the engine and records what came back."""

    def __init__(self, engine, tracer, clock: HostClock, span=None):
        self.engine = engine
        self.tracer = tracer
        self.clock = clock
        self.span = span or (lambda name: nullcontext())
        self.outstanding: Dict[int, traffic_mod.Planned] = {}
        self.steps: List[StepRec] = []
        self._seen = 0

    # -------------------------------------------------------------- io
    def submit(self, p: traffic_mod.Planned, now: float) -> None:
        from repro.serving.engine import Request
        p.submitted = now
        p.handle = Request(p.rid, prompt=list(p.prompt),
                           max_new_tokens=p.max_new)
        adm = self.engine.submit(p.handle)
        if not adm.accepted:
            raise RuntimeError(f"request {p.rid} refused: {adm.reason}")
        self.outstanding[p.rid] = p

    def _new_events(self) -> list:
        ev = self.tracer.events
        total = len(ev) + self.tracer.dropped
        k, self._seen = total - self._seen, total
        k = min(k, len(ev))
        return [ev[-j] for j in range(k, 0, -1)]

    def step(self) -> List[traffic_mod.Planned]:
        """One engine step; returns the requests that finished in it."""
        a = time.perf_counter()
        with self.span("step"):
            dispatched = self.engine.step()
        b = time.perf_counter()
        with self.span("record"):
            events = self._new_events()
            if not dispatched:
                self.steps.append(StepRec(a, b, [], 0, False))
                return []
            prefill = {}
            for e in events:
                if e["name"] == "prefill_chunk":
                    prefill[e["tid"]] = (e["args"]["tokens"],
                                         e["args"]["fed"])
            lanes, emitted, done = [], 0, []
            for rid, p in list(self.outstanding.items()):
                h = p.handle
                before = len(p.token_times)
                new = len(h.out_tokens) - before
                if rid in prefill:
                    lanes.append(prefill[rid])
                elif new:
                    lanes.append((1, len(p.prompt) + before))
                if new:
                    p.token_times.extend([b] * new)
                    emitted += new
                if h.done:
                    done.append(p)
                    del self.outstanding[rid]
            self.steps.append(StepRec(a, b, lanes, emitted))
        return done

    def busy(self) -> bool:
        return bool(self.outstanding) or not self.engine.idle()

    # ---------------------------------------------------------- phases
    def serve_wave(self, wave: List[traffic_mod.Planned],
                   live_second: bool = False) -> None:
        """Serve a set-up wave to completion.  With ``live_second`` the
        second request is submitted once the first has a token, so that
        it maps the first's live pages."""
        first = wave[:1] if live_second else wave
        for p in first:
            self.submit(p, time.perf_counter())
        if live_second:
            while not wave[0].token_times:
                self.step()
            self.submit(wave[1], time.perf_counter())
        while self.busy():
            self.step()

    def offer(self, plan: List[traffic_mod.Planned],
              closed: Optional[int]) -> deque:
        """Start offering the plan's load now: an open loop's arrivals
        from now on by their gaps, a closed loop's first ``closed``
        requests now.  Returns the requests still to submit, for
        ``serve_until``."""
        start = time.perf_counter()
        if closed:
            for p in plan[:closed]:
                p.due = start
        else:
            due = start
            for p in plan:
                p.due = due
                due += p.gap_s
        self.steps.clear()
        return deque(plan)

    def serve_until(self, pending: deque, closed: Optional[int],
                    until: float) -> None:
        """Submit what falls due and step the engine until ``until``."""
        while True:
            now = time.perf_counter()
            if now >= until:
                return
            with self.span("submit"):
                while pending and pending[0].due and pending[0].due <= now:
                    self.submit(pending.popleft(), now)
            if self.busy():
                for _ in self.step():
                    if closed:
                        # a client sends its next request when the last
                        # one is answered
                        nxt = next((p for p in pending if not p.due), None)
                        if nxt is not None:
                            nxt.due = time.perf_counter()
            else:
                nxt = pending[0].due if pending and pending[0].due else until
                with self.span("sleep"):
                    time.sleep(max(0.0, min(nxt, until) - time.perf_counter()))

    def drain(self, limit_s: float) -> bool:
        """Step until every submitted request is done; False if that
        took longer than ``limit_s``."""
        t_end = time.perf_counter() + limit_s
        while self.busy():
            if time.perf_counter() > t_end:
                return False
            self.step()
        return True
