"""Seconds of set-up the program spent constructing its engine and the state
on the device: the sum of the top-level ``setup.*`` phases that the program's
tracer kept before the window opened (``get_tracer().phases``: engine
construction with its stages, the first call of the train step, the KV pool),
other than ``setup.program``. Earlier lines give every phase, and the
``setup.program`` total (the first call of each compiled serving function:
python tracing, lowering, compile or cache load as the host sees it), which
lies beside ``setup_compile_s``."""

from benchmarks.chipbench.harness import say

NAME = "setup_engine_init_s"
UNIT = "s"
LAYER = "device set-up"
MOVES = "setup_s"
KINDS = ("train", "serve_closed")


def read(ctx):
    from deepspeed_tpu.observability.trace import get_tracer
    phases = getattr(get_tracer(), "phases", None)
    if not phases:
        return None
    before = [p for p in phases if p["name"].startswith("setup.")
              and p["t1"] <= ctx.result.window[0]]
    if not before:
        return None
    for p in before:
        if p["name"] != "setup.program":
            attrs = " ".join(f"{k}={v}" for k, v in p["attrs"].items())
            say(f"set-up phase {p['name']}" + (f" (in {p['parent']})" if p["parent"]
                                              else "")
                + f": {p['t1'] - p['t0']:.3f} s, from {p['t0'] - ctx.t0:.3f} s "
                f"after the process started {attrs}".rstrip())
    for top in before:
        stages = [p for p in before if p["parent"] == top["name"]
                  and top["t0"] <= p["t0"] and p["t1"] <= top["t1"]]
        if stages:
            named = sum(p["t1"] - p["t0"] for p in stages)
            say(f"{top['name']}: its stages name {named:.3f} s of "
                f"{top['t1'] - top['t0']:.3f} s; "
                f"{top['t1'] - max(p['t1'] for p in stages):.3f} s follow the last")
    programs = [p for p in before if p["name"] == "setup.program"]
    for p in programs:
        say(f"set-up phase setup.program {p['attrs'].get('program')} bucket "
            f"{p['attrs'].get('bucket')}: {p['t1'] - p['t0']:.3f} s")
    say(f"setup.program total: {sum(p['t1'] - p['t0'] for p in programs):.3f} s "
        f"over {len(programs)} first calls")
    return sum(p["t1"] - p["t0"] for p in before
               if p["parent"] is None and p["name"] != "setup.program")
