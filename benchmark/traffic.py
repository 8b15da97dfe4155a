"""The one traffic generator: a mix's data file + a seed -> a plan of requests.

A mix is a JSON file under ``benchmark/traffic/`` (README.md there). It is
parameters only; this module is the only code that reads them, so a later PR
adds a mix by adding a file. Stdlib only: the load generator imports it and
must never import jax.

Steadiness rule (PERF.md "Noise study"): the *multiset* of sizes and gaps is
a function of the mix file alone. Every length is a stratified quantile of
its distribution, every inter-arrival gap a stratified quantile of the
exponential law, dealt in blocks (``dealt``) so that five sessions, or one
round of a closed loop's clients, hold a like spread of the whole. An open
loop's schedule is periodic with the window's length, so every window holds
exactly the same requests whatever the seed; the seed turns the schedule by
a phase, orders a closed loop's rounds, and writes the bytes of every prompt.
A closed loop's plan is a sequence of such deals (``_closed_plan``: block 0 as
long as the program needs today, then blocks up to what the chip's peaks could
serve), and the rule holds for each of them.
"""

from __future__ import annotations

import json
import math
import random
from pathlib import Path
from statistics import NormalDist

HERE = Path(__file__).resolve().parent

# The byte tokenizer gives one token per byte; answers are held to printable
# ASCII by logit_bias so that one token is one character of streamed text.
_LETTERS = "abcdefghijklmnopqrstuvwxyz"
# template bytes the byte-level chat template adds per message / per prompt:
# "<|role|>\n" + "\n" per message, "<|assistant|>\n" + BOS per prompt
_PER_PROMPT = len("<|assistant|>\n") + 1


def _per_message(role: str) -> int:
    return len("<|{}|>\n\n".format(role))


def load_mix(name: str, directory=None) -> dict:
    path = Path(directory or HERE / "traffic") / (name + ".json")
    if not path.is_file():
        raise FileNotFoundError("no traffic mix file {}".format(path))
    with open(path) as f:
        return json.load(f)


def strata(spec: dict, n: int) -> list:
    """``n`` stratified quantiles (at (i + 0.5) / n) of the distribution
    ``spec`` describes — the same list for every seed."""
    kind = spec["dist"]
    qs = [(i + 0.5) / n for i in range(n)]
    if kind == "fixed":
        out = [spec["value"]] * n
    elif kind == "uniform":
        out = [spec["min"] + q * (spec["max"] - spec["min"]) for q in qs]
    elif kind == "lognormal":
        nd = NormalDist()
        out = [
            spec["median"] * math.exp(spec["sigma"] * nd.inv_cdf(q)) for q in qs
        ]
    elif kind == "exponential":  # mean 1; the caller scales by 1 / rate
        out = [-math.log(1.0 - q) for q in qs]
    elif kind == "zipf":  # values by popularity rank, P(rank r) ~ r**-s
        w = [(r + 1) ** -spec["s"] for r in range(len(spec["values"]))]
        tot, acc, cum = sum(w), 0.0, []
        for x in w:
            acc += x / tot
            cum.append(acc)
        out = [
            spec["values"][next(i for i, c in enumerate(cum) if q <= c + 1e-12)]
            for q in qs
        ]
    elif kind == "choice":  # equal weights, cycled
        out = [spec["values"][i % len(spec["values"])] for i in range(n)]
    else:
        raise ValueError("unknown dist {!r}".format(kind))
    lo, hi = spec.get("min"), spec.get("max")
    if kind in ("lognormal",):
        out = [min(hi, max(lo, v)) for v in out]
    if spec.get("int", kind in ("lognormal", "zipf", "choice", "fixed")):
        out = [int(round(v)) for v in out]
    return out


def dealt(values: list, block: int, rng: random.Random) -> list:
    """The values in blocks of ``block``, each block a like spread of the
    whole (the sorted values are dealt round-robin over the blocks) in an
    order the seed shuffles. Whatever stretch of whole blocks a window
    covers then holds the same work under every seed."""
    values = sorted(values)
    n_blocks = max(1, math.ceil(len(values) / block))
    blocks = [[] for _ in range(n_blocks)]
    for i, v in enumerate(values):
        blocks[i % n_blocks].append(v)
    out = []
    for b in blocks:
        rng.shuffle(b)
        out.extend(b)
    return out


def text(rng: random.Random, n: int) -> str:
    """``n`` bytes of lower-case words. Random letters, so no two prompts
    share a prefix by accident; sharing is what the mix asks for."""
    out = []
    size = 0
    while size < n:
        w = "".join(rng.choice(_LETTERS) for _ in range(rng.randint(2, 9)))
        out.append(w)
        size += len(w) + 1
    return " ".join(out)[:n].ljust(n, ".")


def prompt_tokens(messages: list) -> int:
    """Prompt tokens of a chat request under the byte-level template."""
    return _PER_PROMPT + sum(
        _per_message(m["role"]) + len(m["content"].encode("utf-8"))
        for m in messages
    )


# --------------------------------------------------------------- open loop

def _sessions_plan(mix: dict, config: str, seed: int, ramp: float,
                   window: float, tail: float, rate=None) -> dict:
    """Sessions arriving as a Poisson-like stream on a fixed, periodic schedule.

    The schedule of one period — session starts, turns per session, every
    length and think time — is drawn from the mix file alone
    (``schedule_seed``), and repeats with the period of the window. Any
    stretch of one window's length therefore holds exactly the same requests:
    the same count, sizes and gaps under every seed. The seed turns the
    schedule by a phase (another order) and writes every prompt's bytes.

    The process runs from before t=0, so sessions are in progress when the
    schedule starts: a turn due before 0 is never sent, its answer is a
    stand-in of its pinned length. A turn's due time is fixed: the previous
    turn's due time + a nominal service time + the think time.
    """
    rate = rate or mix["session_rate_per_s"].get(config)
    if rate is None:
        raise KeyError(
            "mix {!r} has no session_rate_per_s for config {!r}: the knee is "
            "per configuration; add a mix file with that rate".format(
                mix["name"], config
            )
        )
    base = random.Random(int(mix.get("schedule_seed", 1)))
    block = int(mix.get("block_sessions", 5))
    n_base = max(1, int(round(rate * window)))
    gaps = dealt(strata({"dist": "exponential"}, n_base), block, base)
    scale = window / sum(gaps)              # n_base sessions in every period
    starts, t = [], 0.0
    for g in gaps:
        starts.append(t + 0.5 * g * scale)
        t += g * scale
    turns = dealt(strata(mix["turns"], n_base), block, base)
    system_ids = dealt(strata(mix["system_prompt"], n_base), block, base)
    n_turns = sum(turns)
    per_block = max(1, n_turns * block // n_base)
    user_lens = dealt(strata(mix["user_tokens"], n_turns), per_block, base)
    out_lens = dealt(strata(mix["answer_tokens"], n_turns), per_block, base)
    thinks = dealt(strata(mix["think_s"], n_turns), per_block, base)
    nominal = mix["nominal_service_s"]
    shapes, k = [], 0           # per session: [(offset, user, answer)] per turn
    for s in range(n_base):
        offset, shape = 0.0, []
        for _ in range(turns[s]):
            shape.append((offset, user_lens[k], out_lens[k]))
            offset += nominal["ttft"] + nominal["per_token"] * out_lens[k] + thinks[k]
            k += 1
        shapes.append(shape)
    longest = max(shape[-1][0] for shape in shapes)

    rng = random.Random(seed)
    phase = rng.uniform(0.0, window)
    systems = [text(random.Random(seed * 1000003 + 17 * i), n)
               for i, n in enumerate(mix["system_prompt_tokens"])]
    end = ramp + window + tail
    first = -int(math.ceil((longest + phase) / window)) - 1
    last = int(math.ceil(end / window)) + 1
    requests = []
    for period in range(first, last + 1):
        for s in range(n_base):
            start = starts[s] - phase + period * window
            if start >= end or start + longest < 0:
                continue
            # what the session said before the schedule starts, system first
            history = [{"role": "system", "content": systems[system_ids[s]]}]
            prev = None
            for turn, (offset, u, o) in enumerate(shapes[s]):
                due = start + offset
                if due >= end:
                    break
                user = {"role": "user", "content": text(rng, u)}
                if due < 0:
                    history = history + [
                        user, {"role": "assistant", "content": text(rng, o)}
                    ]
                    continue
                rid = "s{}p{}t{}".format(s, period - first, turn)
                # a later turn carries only its user message: the client
                # prepends its predecessor's prompt and the answer received
                requests.append({
                    "id": rid, "due": round(due, 6), "after": prev,
                    "messages": ([user] if prev else history + [user]),
                    "max_tokens": o,
                })
                prev = rid
    requests.sort(key=lambda r: (r["due"], r["id"]))
    return {"loop": "open", "requests": requests}


# ------------------------------------------------------------- closed loop

def _closed_block(mix: dict, rng: random.Random, per_client: int, first: int,
                  stagger) -> list:
    """One block of a closed loop's plan: ``per_client`` requests for each of
    the callers, numbered from ``first``. Request k = round * clients +
    client, so every round of the callers holds a like spread of the lengths.
    With ``stagger`` ("answer" or "prompt") a caller's first request of the
    block is cut short by a stratified fraction, so that the callers do not
    move in a wave."""
    clients = int(mix["clients"])
    n = clients * per_client
    p_lens = dealt(strata(mix["prompt_tokens"], n), clients, rng)
    o_lens = dealt(strata(mix["answer_tokens"], n), clients, rng)
    first_cut = dealt(
        strata({"dist": "uniform", "min": 0.0, "max": 1.0}, clients), clients, rng
    )
    out = []
    for c in range(clients):
        seq = []
        for j in range(per_client):
            p, o = p_lens[j * clients + c], o_lens[j * clients + c]
            if j == 0 and stagger:
                cut = 0.15 + 0.85 * first_cut[c]
                if stagger == "answer":
                    o = max(2, int(round(o * cut)))
                else:
                    p = max(16, int(round(p * cut)))
            seq.append({
                "id": "c{}r{}".format(c, first + j),
                "messages": [{"role": "user", "content": text(rng, p)}],
                "max_tokens": o,
            })
        out.append(seq)
    return out


def _closed_plan(mix: dict, config: str, seed: int, horizon: float) -> dict:
    """``clients`` callers, each sending its next request when its last one
    ends, each with a list that outlasts the run however fast the program is.

    The list is a sequence of blocks. Block 0 is sized for the program as it
    is: ``ceil(horizon / nominal_request_s) + 2`` requests a caller, drawn
    from ``random.Random(seed)``, the first of each caller cut short
    (``stagger_first``). Blocks of the same size, each from a generator of its
    own (the seed and the block's index), are appended until a caller holds
    ``ceil(horizon / floor_request_s) + 2`` requests: ``floor_request_s`` is
    the least time a request of the mix could take on the chip (the mix file
    gives the arithmetic). So a run sends, request for request, what it sent
    before there were blocks, a faster program goes on into block 1, and the
    multiset rule of the module's docstring holds block by block. A mix
    without ``floor_request_s`` plans block 0 alone."""
    block = int(math.ceil(horizon / mix["nominal_request_s"])) + 2
    floor = mix.get("floor_request_s")
    need = int(math.ceil(horizon / floor)) + 2 if floor else block
    callers = _closed_block(mix, random.Random(seed), block, 0,
                            mix.get("stagger_first"))
    for b in range(1, int(math.ceil(need / block))):
        more = _closed_block(mix, random.Random(seed * 1000003 + 7919 * b),
                             block, b * block, None)
        for seq, tail in zip(callers, more):
            seq.extend(tail)
    return {"loop": "closed", "clients": callers,
            "block0_per_client": block, "per_client": len(callers[0]),
            "floor_request_s": floor}


def make_plan(mix_name: str, config: str, seed: int, seconds: float,
              directory=None, rate=None) -> dict:
    """The plan of one run: every request the generator may send, with the
    window's edges relative to the start of traffic. Pure in its arguments.
    ``rate`` overrides the mix's session rate (the knee sweep only)."""
    mix = load_mix(mix_name, directory)
    mix.setdefault("name", mix_name)
    ramp = float(mix["ramp_s"])
    drain = float(mix.get("drain_s", 0.0))
    if mix["loop"] == "open":
        # traffic goes on through the drain, so that the last judged requests
        # end under the same load as the first
        plan = _sessions_plan(mix, config, int(seed), ramp, float(seconds),
                              drain, rate)
        margin = float(mix.get("margin_s", 0.0))
        for r in plan["requests"]:
            r["judged"] = ramp <= r["due"] < ramp + float(seconds) - margin
    elif mix["loop"] == "closed":
        plan = _closed_plan(mix, config, int(seed), ramp + float(seconds))
    else:
        raise ValueError("mix loop must be open or closed")
    plan.update({
        "mix": mix_name, "config": config, "seed": int(seed),
        "ramp_s": ramp, "window_s": float(seconds),
        "drain_s": drain,
        "probe_interval_s": float(mix.get("front_probe_interval_s", 0.5)),
    })
    return plan
