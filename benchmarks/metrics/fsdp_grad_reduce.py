"""parallelism: the time per optimizer step (mean over the chips) during which
a gradient-reduction collective is in flight on a chip and no other operation
runs there. The ops are found by BOTH spellings the v5e trace uses: after the
jax primitive where the compiler keeps an authored collective as it is
(`reduce_scatter.<n>`, `ppermute.<n>`), after the compiler where it rebuilds
one (`reduce-scatter.<n>`, `collective-permute-start.<n>` / `-done.<n>`). A
synchronous op is in flight for its own duration, an async pair from the
start op's beginning to the done op's end; other collectives (the weight
all-gathers, the loss's all-reduce) count as other operations. Nothing to
read on one chip, or in a program that reduces no gradient across chips."""

import collections
import re

GRAD_REDUCE = re.compile(r"^(reduce_scatter|reduce-scatter|ppermute|collective-permute)")


def exposed_ns(names, ops, reduce):
    """(in flight, exposed) nanoseconds of one device's ops."""
    flight, other = [], []
    pending = collections.defaultdict(list)
    for n, s, d in reduce.leaf_ops(ops):
        m = GRAD_REDUCE.match(names[n])
        if m is None:
            other.append((s, s + d))
            continue
        rest = names[n][m.end():]
        if rest.startswith("-start"):
            pending[m.group(1)].append(s)
            flight.append((s, s + d))
        elif rest.startswith("-done"):
            begin = pending[m.group(1)].pop(0) if pending[m.group(1)] else s
            flight.append((begin, s + d))
        else:
            flight.append((s, s + d))
    flight = reduce.union(flight)
    return reduce.total(flight), reduce.total(reduce.subtract(flight, reduce.union(other)))


def read(run):
    ts = run.get("trace_summary")
    if run["kind"] != "train" or not ts or run["chips"] < 2 or not run["counters"]["traced_steps"]:
        return None
    reduce = run["load"]("reduce.py")
    names = ts["trace"]["names"]
    flight = exposed = 0
    for dev in ts["devices"]:
        f, e = exposed_ns(names, dev["ops"], reduce)
        flight, exposed = flight + f, exposed + e
    if flight == 0:
        return None
    per_ms = 1.0 / 1e6 / max(1, ts["n_devices"]) / run["counters"]["traced_steps"]
    run["log"](f"fsdp grad reduce: {flight * per_ms:.2f} ms a step a chip in flight, "
               f"{exposed * per_ms:.2f} of it with nothing else running")
    return {"fsdp.grad_reduce_exposed_ms_per_step": exposed * per_ms}
