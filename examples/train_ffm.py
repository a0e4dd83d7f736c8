#!/usr/bin/env python
"""Field-aware FM training example: a libfm file ("label field:idx:val")
-> native parser -> field-staged batches -> FFM SGD.

    python examples/train_ffm.py [--data file.libfm] [--epochs 30]
                                 [--batch-size 4096]

With no --data a synthetic CTR-style dataset is generated whose signal
lives in FIELD PAIRINGS (user x item parity) — a plain FM or linear
model cannot express it, an FFM fits it.  The format is auto-detected
from the .libfm extension; pass ?format=libfm in the URI for other
names.
"""
from __future__ import annotations

import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

def synth_dataset(path: str, rows: int = 20_000, per_field: int = 8) -> int:
    """Two-field interaction problem: y = 1 iff (user + item) is even."""
    import numpy as np
    rng = np.random.default_rng(0)
    with open(path, "w") as f:
        for _ in range(rows):
            u = int(rng.integers(0, per_field))
            i = int(rng.integers(0, per_field))
            y = 1 if (u + i) % 2 == 0 else 0
            f.write(f"{y} 0:{u}:1 1:{per_field + i}:1\n")
    return 2 * per_field  # num_features


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--data", default=None)
    ap.add_argument("--epochs", type=int, default=30)
    ap.add_argument("--batch-size", type=int, default=4096)
    ap.add_argument("--num-fields", type=int, default=0,
                    help="0 = discover from the data (max field id + 1)")
    ap.add_argument("--num-factors", type=int, default=8)
    args = ap.parse_args()

    from dmlc_core_tpu import compile_cache
    from dmlc_core_tpu.data import DeviceStagingIter, Parser
    from dmlc_core_tpu.models import FieldAwareFactorizationMachine

    compile_cache.configure()

    tmp = None
    if args.data is None:
        tmp = tempfile.NamedTemporaryFile(suffix=".libfm", delete=False)
        tmp.close()
        synth_dataset(tmp.name)
        args.data = tmp.name

    # host-only pass sizes BOTH the feature and the field space (no
    # device transfers); silently clamping out-of-range field ids would
    # train a plausible-looking but structurally wrong model
    num_features = 0
    max_field = -1
    with Parser(args.data) as sizing:
        for block in sizing:
            if len(block.index):
                num_features = max(num_features, int(block.index.max()) + 1)
            if block.field is not None and len(block.field):
                max_field = max(max_field, int(block.field.max()))
    if max_field < 0:
        raise SystemExit(f"{args.data} carries no field ids; FFM needs "
                         "libfm 'field:idx:val' triples")
    num_fields = args.num_fields or (max_field + 1)
    if max_field >= num_fields:
        raise SystemExit(
            f"data contains field id {max_field} but --num-fields is "
            f"{num_fields}; fields would be clamped together")
    print(f"{num_features} features, {num_fields} fields")

    ffm = FieldAwareFactorizationMachine(
        num_features=num_features, num_fields=num_fields,
        num_factors=args.num_factors, learning_rate=0.5, init_scale=0.1)
    params = ffm.init(seed=1)
    # ONE staging iterator serves every epoch and the final eval pass
    it = DeviceStagingIter(args.data, batch_size=args.batch_size,
                           with_field=True)
    for epoch in range(args.epochs):
        last = None
        for batch in it:
            params, last = ffm.train_step(params, batch)
        if epoch % max(args.epochs // 5, 1) == 0 or epoch == args.epochs - 1:
            print(f"epoch {epoch}: loss {float(last):.4f}")

    # weighted accuracy over one pass (padding rows carry weight 0)
    import numpy as np
    correct = total = 0.0
    for batch in it:
        pred = np.asarray(ffm.predict(params, batch)) > 0.5
        y = np.asarray(batch.label) > 0.5
        w = np.asarray(batch.weight)
        correct += float(((pred == y) * w).sum())
        total += float(w.sum())
    it.close()
    print(f"final accuracy: {correct / max(total, 1.0):.3f}")
    if tmp is not None:
        os.unlink(tmp.name)


if __name__ == "__main__":
    main()
