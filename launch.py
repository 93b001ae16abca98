"""Training launcher CLI (the reference's primary entry point, launch.py:15-72).

    python launch.py --config=shakespeare_char [--rundir=...] [--debug] \
        [--multihost] [--set key=value ...]

Behavior parity: dynamic config import by name, timestamped rundir default,
config.json persisted to the rundir (local or gs://) for sample-time
reconstruction, wandb-id persistence for resume (when wandb is installed),
cross-host barrier after proc-0 setup, then the supervised train loop
(robustness/supervisor.py: restart-on-divergence + SIGTERM/SIGINT emergency
checkpointing). `--set` dotted overrides (e.g. --set max_steps=100 --set
model_config.n_layer=4) are an addition the reference lacks.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
from datetime import datetime


def apply_overrides(config, pairs):
    """Apply all `--set dotted.key=value` overrides in ONE rebuild.

    Each touched dataclass is replaced exactly once with every override it
    receives, so cross-field validation (__post_init__) sees the final
    state — `--set model_config.attn_impl=flash --set
    model_config.dropout=0.0` works in either order."""
    tree: dict = {}
    for dotted_key, raw_value in pairs:
        parts = dotted_key.split(".")
        target = config
        for p in parts[:-1]:
            target = getattr(target, p)
        current = getattr(target, parts[-1])
        # Optional fields default to None, so the current value's type can't
        # drive parsing — consult the declared annotation (a string under
        # `from __future__ import annotations`) so `--set loss_remat_chunks=0`
        # parses as bool False, not the truthy string '0'.
        fields = getattr(target, "__dataclass_fields__", {})
        ann = str(fields[parts[-1]].type) if parts[-1] in fields else ""
        if raw_value.lower() in ("none", "null"):
            value = None  # tri-state fields (e.g. loss_remat_chunks)
        elif isinstance(current, bool) or "bool" in ann:
            value = raw_value.lower() in ("1", "true", "yes")
        elif raw_value.lower() in ("true", "false"):
            value = raw_value.lower() == "true"
        elif current is not None:
            value = type(current)(raw_value)
        elif "int" in ann:
            value = int(raw_value)  # Optional[int] fields (e.g. n_kv_heads)
        elif "float" in ann:
            value = float(raw_value)
        else:
            value = raw_value
        node = tree
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = value

    def rebuild(obj, node):
        kwargs = {
            k: rebuild(getattr(obj, k), v) if isinstance(v, dict) else v
            for k, v in node.items()
        }
        return dataclasses.replace(obj, **kwargs)

    return rebuild(config, tree)


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", type=str, required=True)
    parser.add_argument("--rundir", type=str)
    parser.add_argument("--debug", action="store_true")
    parser.add_argument("--multihost", action="store_true")
    parser.add_argument(
        "--set",
        action="append",
        default=[],
        metavar="KEY=VALUE",
        help="dotted config override, e.g. --set model_config.n_layer=4",
    )
    args = parser.parse_args()

    import jax

    from midgpt_tpu.utils import compile_cache

    cache_stats = compile_cache.enable()

    if args.multihost:
        jax.distributed.initialize()

    from midgpt_tpu.config import load_config, to_json
    from midgpt_tpu.robustness import preempt
    from midgpt_tpu.robustness.supervisor import supervise

    config = load_config(args.config)
    if args.set:
        config = apply_overrides(
            config, [kv.partition("=")[::2] for kv in args.set]
        )

    if args.rundir is not None:
        config = config.replace(rundir=args.rundir)
    elif not args.debug:
        assert not args.multihost, "multihost runs must prespecify --rundir"
        config = config.replace(
            rundir=os.path.abspath(
                os.path.join("outputs", datetime.now().strftime("%Y-%m-%d-%H-%M-%S"))
            )
        )
    if args.debug:
        config = config.replace(debug=True)

    if jax.process_index() == 0 and not config.debug and config.rundir:
        if config.rundir.startswith("gs://"):
            import gcsfs

            fs = gcsfs.GCSFileSystem()
            fs.makedirs(config.rundir, exist_ok=True)
            with fs.open(os.path.join(config.rundir, "config.json"), "w") as f:
                f.write(to_json(config))
        else:
            os.makedirs(config.rundir, exist_ok=True)
            with open(os.path.join(config.rundir, "config.json"), "w") as f:
                f.write(to_json(config))
        print(f"Writing to {config.rundir}")

    if args.multihost:
        from jax.experimental.multihost_utils import sync_global_devices

        sync_global_devices("end_setup")

    print(config)
    # SIGTERM/SIGINT -> emergency checkpoint at the next step boundary, then
    # a clean exit (a second signal hard-kills). The supervisor adds
    # restart-on-divergence with data-window skip (docs/ROBUSTNESS.md).
    preempt.install_handlers()
    runtime = None
    mesh_product = config.mesh.data * config.mesh.fsdp * config.mesh.sp
    if (
        config.on_resume_mesh == "any"
        and config.mesh.data != -1
        and mesh_product != jax.device_count()
    ):
        # Elastic resume surface (docs/ROBUSTNESS.md "Elastic resume &
        # watchdog"): the configured mesh doesn't fit what the scheduler
        # handed us, and the config opted into topology changes — build the
        # runtime with the data axis re-derived for the ACTUAL device count
        # (the supervisor then reshard-restores the checkpoint through the
        # new mesh's shardings).
        from midgpt_tpu.training.train import make_runtime

        print(
            f"elastic resume: configured mesh wants {mesh_product} device(s), "
            f"found {jax.device_count()}; re-deriving the data axis "
            "(on_resume_mesh='any')"
        )
        runtime = make_runtime(config, devices=list(jax.devices()))
    supervise(config, runtime=runtime)
    print(cache_stats.summary())


if __name__ == "__main__":
    main()
