"""Serving cells: the program's ``ServingEngine`` under a traffic mix.

The engine is driven only through ``submit`` and ``step``; the clock
around each ``step`` is the host's, and every step ends on the engine's
own per-tick sync, so its end is when its tokens exist.  A closed loop
keeps ``clients`` requests in the system (a client sends its next one
as soon as one of its requests retires); an open loop submits each
request when its arrival falls due, whatever the engine is doing.

Weights are made here, on the device in one jitted call from the seed,
in the layout the program's model declares and the dtype it serves,
each leaf by a rule (``RULES``, and the configuration's ``init``).
After the window the run checks what the timed path produced: the page
grants of the allocator, and a sample of finished requests against the
plain reference module the configuration names (``reference``).

Everything of one architecture is in its configuration file, so a new
one is data and modules of its own, with no edit here:

- ``arch``: the program's registered architecture, built and served;
- ``program_sizes``: each key of the model's published configuration
  mapped to the program attribute it must equal; ``not_program_sizes``
  lists the keys the program has no attribute for (``check_arch``);
- ``reference``: a module of ``bench`` with ``SIZES`` (the keys of the
  file it reads), ``CONTROL`` (the ``quant`` of its control) and
  ``served_gaps(params, sizes, prompt, served, pad_to, quant=None)``;
- ``flops``: a module of ``bench`` with ``prefill_flops(sizes, n)`` and
  ``decode_token_flops(sizes, context)``, which ``mfu.*`` reads;
- ``init``: weight rules by leaf name, added to ``RULES`` or in place
  of one of them (``KINDS``);
- ``engine``: arguments of ``ServingEngine`` for every cell, beside the
  traffic file's own ``engine`` geometry.
"""
from __future__ import annotations

import gc
import importlib
import math
import time
from typing import Dict, List

import numpy as np

from bench import stats
from bench import traffic as T
from bench import xplane


def _key(seed: int):
    import jax
    import jax.numpy as jnp
    words = np.random.SeedSequence(seed).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


# the rules every configuration starts from, by leaf name; its ``init``
# adds to them or replaces one.  A leaf no rule names is a matrix, drawn
# normal with variance 1/fan-in, its fan-in the second-to-last axis of
# one layer's slice.
RULES = {"embed": {"embedding": True},
         "scale": {"normal": [1.0, 0.1]},
         "bq": {"normal": [0.0, 0.5]},
         "bk": {"normal": [0.0, 0.5]},
         "bv": {"normal": [0.0, 0.5]}}
# a rule has one of these, and may add ``"then": "log"`` or
# ``"then": "inv_softplus"`` (x + log(1 - exp(-x))):
#   embedding: N(0, 1/hidden_size), rows past vocab_size zero
#   const: c;  normal: [mean, std];  uniform: [low, high]
#   log_uniform: [low, high], uniform in log
KINDS = ("embedding", "const", "normal", "uniform", "log_uniform")
THEN = ("log", "inv_softplus")


def init_rules(sizes: dict) -> dict:
    """The configuration's weight rules over ``RULES``, each checked."""
    rules = dict(RULES, **sizes.get("init", {}))
    for name, r in rules.items():
        kind = [k for k in r if k != "then"]
        if (len(kind) != 1 or kind[0] not in KINDS
                or r.get("then", THEN[0]) not in THEN):
            raise ValueError(f"init rule {name!r} is not one of {KINDS} "
                             f"with an optional then in {THEN}: {r}")
    return rules


def _draw(key, sd, fan, rule, sizes):
    """One leaf of shape ``sd`` by ``rule`` (None: over its fan-in);
    ``fan`` is its shape less the layer axis."""
    import jax
    import jax.numpy as jnp

    if rule is None:
        if len(fan) < 2:
            raise ValueError("a vector leaf needs a rule under init")
        return jax.random.normal(key, sd.shape, jnp.float32) * fan[-2] ** -0.5
    (kind, arg), = ((k, v) for k, v in rule.items() if k != "then")
    if kind == "embedding":
        z = jax.random.normal(key, sd.shape, jnp.float32)
        rows = jnp.arange(sd.shape[0])[:, None] < sizes["vocab_size"]
        x = jnp.where(rows, z * sizes["hidden_size"] ** -0.5, 0.0)
    elif kind == "const":
        x = jnp.full(sd.shape, float(arg), jnp.float32)
    elif kind == "normal":
        mean, std = arg
        x = std * jax.random.normal(key, sd.shape, jnp.float32)
        if mean:
            x = mean + x
    else:
        lo, hi = ((math.log(a) for a in arg) if kind == "log_uniform"
                  else arg)
        x = jax.random.uniform(key, sd.shape, jnp.float32, lo, hi)
        if kind == "log_uniform":
            x = jnp.exp(x)
    if rule.get("then") == "log":
        x = jnp.log(x)
    elif rule.get("then") == "inv_softplus":
        x = x + jnp.log(-jnp.expm1(-x))
    return x


def make_params(shapes, axes, sizes: dict, seed: int):
    """Random weights for a tree of ``ShapeDtypeStruct`` (the program's
    layout) whose logical axes are ``axes`` (a stacked leaf's layer axis
    is named ``layers``), one jitted call on the device, each leaf by
    its rule (``init_rules``)."""
    import jax

    leaves, tree = jax.tree_util.tree_flatten_with_path(shapes)
    leaf_axes = jax.tree.leaves(axes,
                                is_leaf=lambda x: isinstance(x, tuple))
    rules = init_rules(sizes)

    def init(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, (path, sd), ax in zip(keys, leaves, leaf_axes):
            name = str(getattr(path[-1], "key", path[-1]))
            fan = [n for n, a in zip(sd.shape, ax) if a != "layers"]
            try:
                x = _draw(k, sd, fan, rules.get(name), sizes)
            except ValueError as e:
                raise ValueError(f"{jax.tree_util.keystr(path)}: {e}")
            out.append(x.astype(sd.dtype))
        return jax.tree_util.tree_unflatten(tree, out)

    return jax.jit(init)(_key(seed))


# keys of a serving configuration file that are the harness's own; every
# other key is the model's, compared with the program or listed as not
# the program's
HARNESS_KEYS = frozenset((
    "system", "arch", "source", "reference", "flops", "init",
    "program_sizes", "not_program_sizes", "param_dtype", "kv_dtype",
    "compute_dtype", "engine", "deployment", "assumed"))


def check_arch(cfg, sizes: dict):
    """The program's architecture must be the configuration file's: each
    key in ``program_sizes`` equals the program attribute it names, and
    every key of the model is either there or in ``not_program_sizes``."""
    mapped = sizes["program_sizes"]
    loose = (set(sizes) - HARNESS_KEYS - set(mapped)
             - set(sizes["not_program_sizes"]))
    missing = set(mapped) - set(sizes)
    if loose or missing:
        raise ValueError(f"configuration keys neither mapped to the program "
                         f"nor listed as not its sizes: {sorted(loose)}; "
                         f"mapped but not in the file: {sorted(missing)}")
    got = {k: getattr(cfg, a) for k, a in mapped.items()}
    bad = {k: (v, sizes[k]) for k, v in got.items()
           if (list(v) if isinstance(v, tuple) else v) != sizes[k]}
    if bad:
        raise ValueError(f"program arch {cfg.name!r} differs from the "
                         f"configuration file: {bad}")


def reference(sizes: dict):
    """The configuration's reference module and the sizes it reads."""
    ref = importlib.import_module("bench." + sizes["reference"])
    return ref, {k: sizes[k] for k in ref.SIZES}


class Driver:
    """Submits, steps and records.  Times are host seconds on one
    monotonic clock; step indices count every ``step`` of the run."""

    def __init__(self, eng, reqs: List[T.Req]):
        self.eng, self.reqs = eng, reqs
        self.uid2i: Dict[int, int] = {}
        self.rec = [self.blank(i) for i in range(len(reqs))]
        self.steps: List[dict] = []
        self.retired_tokens = 0

    @staticmethod
    def blank(i: int) -> dict:
        return dict(i=i, due=None, submit=None, admit_step=None,
                    admit0=None, admit1=None, retire_step=None,
                    retire=None, n_out=None, out=None, prompt_len=None)

    def submit(self, i: int, now: float):
        r = self.reqs[i]
        uid = self.eng.submit(r.prompt, max_new_tokens=r.max_new)
        self.uid2i[uid] = i
        self.rec[i].update(submit=now, prompt_len=len(r.prompt))

    def emitted(self) -> int:
        """Output tokens produced so far: retired requests' tokens plus
        each live slot's generated length."""
        eng, n = self.eng, self.retired_tokens
        for s, r in enumerate(eng.slot_req):
            if r is not None:
                n += int(eng.slot_len[s]) - len(r.prompt)
        return n

    def busy(self) -> bool:
        return bool(self.eng.waiting) or any(
            r is not None for r in self.eng.slot_req)

    def step(self) -> list:
        import jax
        eng = self.eng
        waiting = {r.uid for r in eng.waiting}
        active = sum(r is not None for r in eng.slot_req)
        k = len(self.steps)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.step"):
            fin = eng.step()
        t1 = time.perf_counter()
        admitted = waiting - {r.uid for r in eng.waiting}
        for uid in admitted:
            rec = self.rec[self.uid2i[uid]]
            rec.update(admit_step=k, admit0=t0, admit1=t1)
        for r in fin:
            rec = self.rec[self.uid2i[r.uid]]
            rec.update(retire_step=k, retire=t1, n_out=len(r.out_tokens),
                       out=list(r.out_tokens))
            self.retired_tokens += len(r.out_tokens)
        self.steps.append(dict(t0=t0, t1=t1, active=active,
                               admitted=len(admitted),
                               emitted=self.emitted(),
                               waiting=len(eng.waiting)))
        return fin


def _engine(cell: dict, seed: int):
    import jax
    import jax.numpy as jnp
    from repro.configs import get_arch
    from repro.models.model import build_model
    from repro.serve.engine import ServingEngine

    sizes, mix = cell["config"], cell["mix"]
    cfg = get_arch(sizes["arch"])
    check_arch(cfg, sizes)
    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    if any(x.dtype != jnp.dtype(sizes["param_dtype"])
           for x in jax.tree.leaves(shapes)):
        raise ValueError("program parameters are not in the configured "
                         f"{sizes['param_dtype']}")
    params = make_params(shapes, model.logical_axes(), sizes, seed)
    eng = ServingEngine(
        model, params, kv_dtype=jnp.dtype(sizes["kv_dtype"]),
        compute_dtype=jnp.dtype(sizes["compute_dtype"]),
        **sizes["engine"], **mix["engine"])
    return params, eng


def _n_requests(mix: dict, seconds: float) -> int:
    if mix["loop"] == "closed":
        # every client retires at most once per tick; a generous bound
        return mix["clients"] * 64 + int(mix.get("block", 64))
    span = mix["lead_in_s"] + seconds + mix["drain_s"] + 5.0
    return int(math.ceil(mix["rate_per_s"] * span)) + 2 * mix["block"]


def run(cell: dict, seed: int, seconds: float, traced: bool, t_start: float,
        counter, devs, control: bool = False) -> dict:
    """One run of a serving cell; returns the run record the metric
    readers and the checks read."""
    sizes, mix = cell["config"], cell["mix"]
    reqs = T.requests(mix, seed, _n_requests(mix, seconds),
                      sizes["vocab_size"])
    allowed = set(T.allowed_prompt_lengths(mix))
    assert all(len(r.prompt) in allowed for r in reqs)
    params, eng = _engine(cell, seed)
    drv = Driver(eng, reqs)
    del eng             # the driver holds the one reference, freed later
    out = dict(system="serving", steps=drv.steps, rec=drv.rec, mix=mix,
               sizes=sizes, max_batch=mix["engine"]["max_batch"])
    if mix["loop"] == "closed":
        _closed(drv, mix, seconds, traced, counter, out)
    else:
        _open(drv, mix, seconds, traced, counter, out)
    out["setup_s"] = out["window"][0] - t_start
    k0, k1 = out["window_steps"]
    # where a window lost its time: the longest steps, with what they
    # did, and the tokens per second of each fifth of the window
    out["slowest_steps"] = sorted(
        ([round(s["t1"] - s["t0"], 4), s["admitted"], s["active"]]
         for s in drv.steps[k0:k1]), reverse=True)[:3]
    st = drv.steps
    out["segments"] = stats.segment_rates(
        ((st[k]["t1"], st[k]["emitted"] - st[k - 1]["emitted"])
         for k in range(max(k0, 1), k1)), *out["window"])
    from bench import device
    out["device"] = device.describe(devs, out.get("trace"))
    out["checks"] = _checks(drv, params, cell, seed, out, control)
    return out


def _run_until(drv, t_end: float, feed):
    while time.perf_counter() < t_end:
        feed()


def _closed(drv, mix, seconds, traced, counter, out):
    """Closed loop: warm every shape the cell uses (the first wave's
    prefills, the tick, a retirement and a re-admission), then measure."""
    clients = mix["clients"]
    state = {"next": clients}
    for i in range(clients):
        drv.submit(i, time.perf_counter())

    def feed():
        for r in drv.step():
            if state["next"] < len(drv.reqs):
                drv.submit(state["next"], time.perf_counter())
                state["next"] += 1

    feed()
    while not any(s["admitted"] for s in drv.steps[1:]) or len(
            drv.steps) < 4:
        feed()
    t0 = time.perf_counter()
    k0, e0 = len(drv.steps), drv.emitted()
    counter.armed = True
    if traced:
        _traced_end(drv, feed, out, t0, k0, t0 + seconds, mix["trace_s"])
    else:
        _run_until(drv, t0 + seconds, feed)
    counter.armed = False
    t1 = time.perf_counter()
    out.update(window=(t0, t1), window_steps=(k0, len(drv.steps)),
               tokens=drv.emitted() - e0)
    live = sum(r is not None for r in drv.eng.slot_req)
    done = [r for r in drv.rec
            if r["retire"] is not None and t0 <= r["retire"] <= t1]
    out["attempted"] = len(done) + live
    out["failed"] = int(drv.eng.stats["evictions"]
                        + drv.eng.stats["alloc_failures"])
    out["sample_pool"] = done


def _traced_end(drv, feed, out, t0, k0, t_end, trace_s, limit=None):
    """Run the window untraced, then trace its last ``trace_s`` seconds
    (on to the first admission, which ``admit_ms_per_req`` reads, before
    ``limit``).  The profiler slows the host while it records and
    stalls it while it stops, so the metrics read on the harness clock
    take only the part before the trace (``out["harness"]``)."""
    tc = t_end - trace_s
    _run_until(drv, tc, feed)
    kc = len(drv.steps)
    out["harness"] = ((t0, time.perf_counter()), (k0, kc))

    def part():
        _run_until(drv, t_end, feed)
        while (limit is not None and time.perf_counter() < limit
               and not any(s["admitted"] for s in drv.steps[kc:])):
            feed()

    out["trace"] = xplane.capture(part)
    out["trace_steps"] = (kc, len(drv.steps))


def _open(drv, mix, seconds, traced, counter, out):
    """Open loop: warm each prompt length and the tick, then submit
    requests as they fall due; the window's requests are followed to
    their end while arrivals go on, up to ``drain_s`` past its close."""
    warm = T.allowed_prompt_lengths(mix)
    rng = np.random.default_rng(0)
    base = len(drv.reqs)
    for j, lp in enumerate(warm):
        drv.reqs.append(T.Req(rng.integers(
            2, out["sizes"]["vocab_size"], lp).astype(np.int32), 2))
        drv.rec.append(drv.blank(base + j))
        drv.submit(base + j, time.perf_counter())
    while drv.busy():
        drv.step()
    _warm_release(drv.eng, drv.reqs)
    a = time.perf_counter() + 0.05
    due = [a + r.due_s for r in drv.reqs[:base]]
    w0 = a + mix["lead_in_s"]
    w1 = w0 + seconds
    win = [i for i in range(base) if w0 <= due[i] < w1]
    for i in range(base):
        drv.rec[i]["due"] = due[i]
    state = {"next": 0}

    def feed():
        now = time.perf_counter()
        while state["next"] < base and due[state["next"]] <= now:
            drv.submit(state["next"], now)
            state["next"] += 1
        if drv.busy():
            drv.step()
        elif state["next"] < base:
            import jax
            with jax.profiler.TraceAnnotation("bench.wait_arrival"):
                time.sleep(max(0.0, min(due[state["next"]] - now, 0.005)))

    _run_until(drv, w0, feed)
    counter.armed = True
    k0 = len(drv.steps)
    limit = w1 + mix["drain_s"]
    if traced:
        _traced_end(drv, feed, out, w0, k0, w1, mix["trace_s"], limit)
    else:
        _run_until(drv, w1, feed)
    k1 = len(drv.steps)
    while (time.perf_counter() < limit
           and any(drv.rec[i]["retire"] is None for i in win)):
        feed()
    counter.armed = False
    out.update(window=(w0, w1), window_steps=(k0, k1), window_reqs=win,
               lateness_s=max([drv.rec[i]["submit"] - due[i] for i in win
                               if drv.rec[i]["submit"] is not None],
                              default=0.0))
    done = [drv.rec[i] for i in win if drv.rec[i]["retire"] is not None]
    out["attempted"] = len(win)
    out["failed"] = len(win) - len(done)
    out["sample_pool"] = done


def _warm_release(eng, reqs):
    """Warm the free transaction of every request the run can retire.
    The engine frees a retiring request's pages in one transaction of
    ``max(2 * max_batch, pages)`` lanes (``ServingEngine._bulk_free``),
    and a request of ``lp`` prompt and ``n`` output tokens retires
    holding ``ceil((lp + n) / page)`` pages, so each page count past
    ``2 * max_batch`` is a program of its own.  Each is compiled (or
    loaded from the cache) here, on a copy of the arena with every lane
    masked off, and not inside the window."""
    import jax
    import jax.numpy as jnp

    counts = {-(-(len(r.prompt) + r.max_new) // eng.page) for r in reqs}
    st = None
    for lanes in sorted(c for c in counts if c > 2 * eng.max_batch):
        offs = np.full(lanes, -1, np.int32)
        st = eng.ouro.free(jax.tree.map(jnp.copy, eng.alloc_state),
                           jnp.asarray(offs),
                           jnp.full(lanes, eng.page_bytes, jnp.int32),
                           jnp.asarray(offs >= 0))
    jax.block_until_ready(st)


def _checks(drv, params, cell, seed, out, control) -> dict:
    """What the timed path produced, each number beside its limit."""
    eng, mix, sizes = drv.eng, cell["mix"], cell["config"]
    lim = mix["limits"]
    # every page a slot holds: its page-table pages (none where the
    # program keeps no KV) and its state pages
    kv = eng._kv()
    pt = np.asarray(kv.page_table) if kv is not None else np.zeros(0, int)
    live = np.concatenate([pt[pt >= 0].astype(np.int64), np.asarray(
        [p for aux in eng.slot_aux for p in aux], np.int64)])
    checks = {
        "page_dups": (int(live.size - np.unique(live).size), 0),
        "page_balance": (abs(int(eng.stats["allocs"]) - int(
            eng.stats["frees"]) - int(live.size)), 0),
        "alloc_failures": (int(eng.stats["alloc_failures"]), 0),
    }
    pool = out.pop("sample_pool")
    rng = np.random.default_rng([seed, 1])
    pool = sorted(pool, key=lambda r: (-r["n_out"], r["retire"]))
    k = min(mix["check_requests"], len(pool))
    pick = pool[:1] + [pool[i] for i in sorted(
        rng.choice(np.arange(1, len(pool)), size=max(k - 1, 0),
                   replace=False))] if pool else []
    prompts = {id(r): drv.reqs[r["i"]].prompt for r in pick}
    out["compared_tokens"] = int(sum(r["n_out"] for r in pick))
    # free the program's state before the reference runs: a process's
    # peak never falls again, and the peak was read before this
    drv.eng = None
    del eng
    gc.collect()
    ref, ref_sizes = reference(sizes)
    gap, ctl = 0.0, None
    t0 = time.perf_counter()
    for r in pick:
        n = len(prompts[id(r)]) + r["n_out"]
        pad = -(-n // 1024) * 1024
        g = ref.served_gaps(params, ref_sizes, prompts[id(r)], r["out"], pad)
        gap = max(gap, float(g.max()))
        if control:
            c = ref.served_gaps(params, ref_sizes, prompts[id(r)], r["out"],
                                pad, quant=ref.CONTROL)
            ctl = max(ctl or 0.0, float(c.max()))
    out["reference_s"] = time.perf_counter() - t0
    if not pick:
        gap = 1e30              # nothing finished: nothing proven
    out["program_gap"] = gap
    # the control stands in the program's place: its gap is the one judged
    checks["logit_gap"] = (ctl if control and pick else gap,
                           lim["logit_gap"])
    return checks
