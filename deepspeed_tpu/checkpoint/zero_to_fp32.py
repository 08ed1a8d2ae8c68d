"""zero_to_fp32: offline fp32 weight reconstruction from a checkpoint.

Reference: ``deepspeed/utils/zero_to_fp32.py`` — the standalone script the
engine copies into every checkpoint directory (engine._copy_recovery_script
:3991) so users can rebuild a consolidated fp32 state dict from per-rank
ZeRO shard files without the training stack.

TPU form: checkpoints are orbax global-array stores, so "reconstruction" is
a single restore on CPU (no shard-merging arithmetic — orbax reassembles the
global arrays) followed by an fp32 cast of the half-precision params. When
the checkpoint carries the optimizer's fp32 master weights, those are
preferred — they are the exact values, not a bf16 round trip.

Usage (standalone, no TPU needed):
    python zero_to_fp32.py <checkpoint_dir> <output_file> [--tag TAG]
Produces an .npz mapping dotted parameter names to fp32 numpy arrays
(loadable with np.load; keys match save_16bit_model's layout).
"""

import argparse
import json
import os
import sys


def _flatten(prefix, tree, out):
    # key scheme matches checkpoint/engine.py save_16bit_model exactly
    # (including its unconditional ".{i}" for sequences) so the two .npz
    # exports line up key-for-key
    if isinstance(tree, dict):
        for k, v in tree.items():
            _flatten(f"{prefix}.{k}" if prefix else str(k), v, out)
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            _flatten(f"{prefix}.{i}", v, out)
    elif hasattr(tree, "shape"):
        out[prefix] = tree
    return out


def get_fp32_state_dict_from_zero_checkpoint(checkpoint_dir, tag=None):
    """Reference-parity function name. Returns {dotted_name: fp32 ndarray}."""
    import numpy as np

    import jax
    import orbax.checkpoint as ocp

    # A host-side converter: it runs on a login node, or beside a live trainer
    # that owns the chip, so it pins itself to the CPU before any backend
    # comes up (in a process that already has one, the pin is a no-op and
    # the CPU device below is still the one used).
    jax.config.update("jax_platforms", "cpu")

    if tag is None:
        latest = os.path.join(checkpoint_dir, "latest")
        if not os.path.isfile(latest):
            raise FileNotFoundError(f"no 'latest' in {checkpoint_dir}; pass --tag")
        tag = open(latest).read().strip()
    state_path = os.path.abspath(os.path.join(checkpoint_dir, str(tag), "state"))
    if not os.path.exists(state_path):
        raise FileNotFoundError(state_path)
    with ocp.StandardCheckpointer() as ckptr:
        # restore against THIS host's devices (the checkpoint was written by a
        # different topology — the whole point of an offline converter): build
        # an abstract target from the stored metadata, everything on one CPU
        # device
        meta = ckptr.metadata(state_path)
        # orbax wraps the item pytree in StepMetadata on recent versions
        meta = getattr(meta, "item_metadata", meta)
        sharding = jax.sharding.SingleDeviceSharding(jax.devices("cpu")[0])

        def abstr(m):
            shape = getattr(m, "shape", None)
            dtype = getattr(m, "dtype", None)
            if shape is None or dtype is None:
                return m
            return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)

        target = jax.tree.map(abstr, meta)
        # prune the Adam moments: this script needs params + fp32 masters
        # only, and reads ~5x the param bytes otherwise (None subtrees are
        # skipped by the restore, matching the engine's template semantics)
        pruned = dict(target) if isinstance(target, dict) else target
        opt = pruned.get("opt_state") if isinstance(pruned, dict) else None
        if isinstance(opt, dict) and "master" in opt:
            pruned["opt_state"] = {
                k: (v if k == "master" else None) for k, v in opt.items()
            }
        if isinstance(pruned, dict) and "scaler_state" in pruned:
            pruned["scaler_state"] = None
        try:
            restored = ckptr.restore(state_path, pruned)
        except Exception as e:  # noqa: BLE001 — orbax's refusal type varies
            # by version for partial (None-subtree) targets; surface the
            # cause, then pay for the full read (which re-raises real errors)
            print(f"partial restore failed ({type(e).__name__}: {e}); "
                  "reading full state", file=sys.stderr)
            restored = ckptr.restore(state_path, target)

    params = restored.get("params", {})
    flat_params = _flatten("", params, {})
    # prefer exact fp32 masters when the optimizer state carries them
    masters = {}
    opt = restored.get("opt_state")
    if isinstance(opt, dict) and "master" in opt:
        masters = _flatten("", opt["master"], {})
    elif hasattr(opt, "master"):  # OptState namedtuple survives as dict/obj
        masters = _flatten("", opt.master, {})
    elif isinstance(opt, (list, tuple)) and opt and isinstance(opt[0], dict):
        pass  # unknown layout: fall back to casting params

    out = {}
    for name, arr in flat_params.items():
        src = masters.get(name, arr)
        out[name] = np.asarray(jax.device_get(src)).astype(np.float32)
    return out


def convert_zero_checkpoint_to_fp32_state_dict(checkpoint_dir, output_file, tag=None):
    import numpy as np

    sd = get_fp32_state_dict_from_zero_checkpoint(checkpoint_dir, tag)
    if not output_file.endswith(".npz"):
        output_file += ".npz"
    np.savez(output_file, **sd)
    total = sum(v.size for v in sd.values())
    print(json.dumps({"output": output_file, "tensors": len(sd), "params": total}))
    return output_file


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        "zero_to_fp32", description="Reconstruct consolidated fp32 weights from a checkpoint"
    )
    p.add_argument("checkpoint_dir")
    p.add_argument("output_file")
    p.add_argument("--tag", default=None)
    args = p.parse_args(argv)
    convert_zero_checkpoint_to_fp32_state_dict(args.checkpoint_dir, args.output_file, args.tag)
    return 0


if __name__ == "__main__":
    sys.exit(main())
