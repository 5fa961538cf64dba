"""Data-parallel training traffic: traffic `train`'s `lgb.Dataset` ->
`lgb.train` -> `Booster.update()` with `tree_learner=data` in the
configuration's params, on every chip of the host.

Set-up, window and traced sub-window are modes/train.py's own functions;
so is the whole check, whose reference (reference/gbdt_numpy.py) knows
nothing of shards: the guarantee of data parallel is the serial learner's
tree on the same table, so tree 0's leaf counts are held to numpy's
routing of EVERY row of every shard, and a shard dropped, doubled or
summed twice fails them. This file adds what only a run on several chips
can show: that the run was one (learner, tier, kernels, how the code
planes were packed), that each chip holds its share of the lanes and no
more than its share of the memory, and that the program made no blocking
sync of its own.

Where the process has fewer devices than the cell's `chips` (the
one-device rehearsal of benchmarks/tests/test_rehearsal.py; the measuring
command refuses such a process), `tree_learner=data` is the serial fused
learner and these checks say so and hold nothing.

What this file reads of the program beyond modes/train.py's list:
`bst._gbdt.device_score_state()`'s sharding and `Device.memory_stats()`.
"""
from __future__ import annotations

from benchmarks.harness import loader

_train = loader.load_module("modes", "train")
State, setup, window = _train.State, _train.setup, _train.window

PEAK_FACTOR = 2.0       # chip_smoke.stage_four_chip's bound: the global
                        # state assembled on one chip first reads ~4x


def traced(ctx, st) -> dict:
    out = _train.traced(ctx, st)
    st.artifacts["blocking_syncs"] = out["counters"]["blocking_syncs"]
    return out


def _plan_check(plan: dict, chips: int) -> tuple:
    want = {"learner": "FusedDataParallelGrower", "device_count": chips,
            "tier": "persistent-fused"}
    ok = all(plan.get(k) == v for k, v in want.items())
    if plan["backend"] == "tpu":
        ok = ok and "pallas" in plan["hist"] and "pallas" in plan["partition"]
    # a program from before the plan said so packed on the device and is
    # not held to it here; one that says so has to say "host"
    ok = ok and plan.get("codes_pack", "host") == "host"
    return ("dp_plan", ok,
            f"learner {plan.get('learner')}, device_count "
            f"{plan.get('device_count')}, tier {plan.get('tier')}, hist "
            f"{plan.get('hist')}, partition {plan.get('partition')}, code "
            f"planes packed on: {plan.get('codes_pack', 'not stated')}, "
            f"shard_rows {plan.get('shard_rows', 'not stated')}")


def _sharding_check(state, plan: dict, rows: int, chips: int) -> tuple:
    shard_rows = -(-rows // chips)
    held = sorted((str(s.device), s.data.shape[1])
                  for s in state.addressable_shards)
    lanes = state.shape[1] // chips
    ok = (len({d for d, _ in held}) == chips
          and all(n == lanes for _, n in held) and lanes >= shard_rows
          and plan.get("shard_rows", shard_rows) == shard_rows)
    return ("dp_sharding", ok,
            f"state {tuple(state.shape)}: {lanes} lanes for {shard_rows} "
            f"rows a chip on {len(held)} chips {held}")


def _memory_check() -> tuple:
    import jax
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in jax.devices()]
    if not min(peaks):      # a backend without the statistic (CPU)
        return ("dp_memory_balance", True, "no memory statistic here")
    factor = max(peaks) / min(peaks)
    return ("dp_memory_balance", factor <= PEAK_FACTOR,
            f"peak_bytes_in_use per chip {peaks}: fullest / least "
            f"{factor:.3f} (allowed {PEAK_FACTOR})")


def check(ctx, st) -> list:
    import jax
    chips = int(ctx.cell["chips"])
    plan = st.bst._gbdt.execution_plan()
    if len(jax.devices()) != chips:
        return _train.check(ctx, st) + [
            ("dp_plan", plan["tier"] == "persistent-fused",
             f"{len(jax.devices())} device(s) for a cell of {chips}: "
             f"learner {plan['learner']}, nothing sharded, nothing held")]
    # the chips' peaks as training left them, before the check's own
    # score sync, evaluation and predict
    memory = _memory_check()
    out = _train.check(ctx, st)
    out += [_plan_check(plan, chips),
            _sharding_check(st.bst._gbdt.device_score_state(), plan,
                            st.rows, chips),
            memory]
    if "blocking_syncs" in st.artifacts:
        syncs = st.artifacts["blocking_syncs"]
        out.append(("dp_blocking_syncs", syncs == 0,
                    f"{syncs} blocking syncs of the program's own in the "
                    "traced sub-window"))
    return out
