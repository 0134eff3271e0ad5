"""Executable runner: ``python -m deeplearning4j_tpu <command> ...``.

The CLI surface the reference exposes through
``DeepLearning4jDistributedApp.main``
(``scaleout/actor/runner/DeepLearning4jDistributedApp.java:60,166`` — train
from a JSON conf, master/worker cluster roles) and the YARN ``Client``/
``Kill`` CLIs, mapped to the TPU-native runtime:

- ``train``      — build a MultiLayerNetwork from a JSON conf (``-json`` /
                   ``-jsonpath`` parity) or a zoo preset, fit on a named
                   dataset, report F1, optionally save the model.
- ``evaluate``   — load a saved model, evaluate on a named dataset.
- ``scaleout``   — run the master role of the multi-process scaleout runtime
                   (jobs from a text file, one per line), or a single worker
                   joining an existing state directory (``-t`` parity).
- ``dryrun``     — the multi-chip sharding dryrun on n virtual devices.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path


def _dataset(name: str, batch: int):
    from .datasets import (DigitsDataSetIterator, IrisDataSetIterator,
                           MnistDataSetIterator)
    name = name.lower()
    if name == "iris":
        it = IrisDataSetIterator(batch=batch)
    elif name == "digits":
        it = DigitsDataSetIterator(batch=batch)
    elif name == "mnist":
        it = MnistDataSetIterator(batch=batch)
    else:
        raise SystemExit(f"unknown dataset {name!r} (iris|digits|mnist)")
    ds = it.next()
    return ds.normalize_zero_mean_unit_variance().shuffle(seed=42)


def _cmd_train(args) -> int:
    import jax

    from .nn import MultiLayerNetwork
    from .nn.conf import MultiLayerConfiguration

    if args.json:
        conf = MultiLayerConfiguration.from_json(args.json)
    elif args.jsonpath:
        conf = MultiLayerConfiguration.from_json(Path(args.jsonpath).read_text())
    else:
        from .models import zoo
        builders = {"mlp": lambda n_in, n_out: zoo.mlp(
                        n_in, n_out, num_iterations=args.iterations),
                    "dbn": lambda n_in, n_out: zoo.dbn(
                        n_in, n_out, finetune_iterations=args.iterations)}
        if args.model not in builders:
            raise SystemExit(f"unknown --model {args.model!r} (mlp|dbn) "
                             "— or pass -json/-jsonpath")
        ds = _dataset(args.dataset, args.batch)
        net = builders[args.model](ds.features.shape[-1], ds.labels.shape[-1])
        conf = None

    if conf is not None:
        ds = _dataset(args.dataset, args.batch)
        net = MultiLayerNetwork(conf)
    net.init(jax.random.key(args.seed))
    net.fit(ds)
    ev = net.evaluate(ds)
    print(ev.stats())
    if args.out:
        net.save(args.out)
        print(f"model saved to {args.out}")
    return 0


def _cmd_evaluate(args) -> int:
    from .nn import MultiLayerNetwork
    net = MultiLayerNetwork.load(args.model_path)
    ds = _dataset(args.dataset, args.batch)
    print(net.evaluate(ds).stats())
    return 0


def _cmd_scaleout(args) -> int:
    if args.type == "kill":
        # the YARN Kill CLI analog: raise the DONE flag so the master loop
        # and every worker process wind down at their next poll
        from .parallel.procstate import FileStateTracker
        FileStateTracker(args.state_dir).finish()
        print(f"kill signalled in {args.state_dir}")
        return 0
    if args.type == "worker":
        from .parallel.procrunner import worker_loop
        worker_loop(args.state_dir, args.worker_id, args.performer)
        return 0
    from .parallel.performers import WordCountRouter
    from .parallel.procrunner import ProcessDistributedRunner
    from .parallel.scaleout import CollectionJobIterator
    if not args.jobs:
        raise SystemExit("--jobs is required for the master role")
    lines = [ln for ln in Path(args.jobs).read_text().splitlines() if ln.strip()]
    router = (WordCountRouter if args.router == "wordcount" else None)
    kw = {"router_cls": router} if router else {}
    runner = ProcessDistributedRunner(
        CollectionJobIterator(lines), args.performer,
        state_dir=args.state_dir, n_workers=args.workers, **kw)
    result = runner.run(max_wall_s=args.max_wall_s)
    print(json.dumps(result if not hasattr(result, "items")
                     else dict(result), default=str))
    return 0


def _cmd_provision(args) -> int:
    from .parallel.provision import PodSliceProvisioner, PodSliceSpec
    prov = PodSliceProvisioner(PodSliceSpec(
        name=args.name, accelerator_type=args.accelerator_type,
        zone=args.zone, spot=args.spot))
    if args.kill:
        rec = prov.teardown(dry_run=not args.apply)
        print(json.dumps(rec))
        return 0
    if not args.repo_url:
        raise SystemExit("--repo-url is required unless --kill")
    if args.apply or args.dry_run_apply:
        records = prov.apply(args.repo_url, args.train_argv,
                             dry_run=not args.apply)
        for rec in records:
            print(json.dumps(rec))
        return 0
    if args.out:
        path = prov.write_script(args.out, args.repo_url, args.train_argv)
        print(f"wrote {path}")
    else:
        print(prov.render_script(args.repo_url, args.train_argv))
    return 0


def _cmd_dryrun(args) -> int:
    from .parallel.dryrun import dryrun_multichip
    dryrun_multichip(args.devices)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m deeplearning4j_tpu")
    sub = ap.add_subparsers(dest="command", required=True)

    t = sub.add_parser("train", help="fit a network on a named dataset")
    t.add_argument("--model", default="mlp", help="zoo preset (mlp|dbn)")
    t.add_argument("-json", dest="json", help="MultiLayerConfiguration JSON")
    t.add_argument("-jsonpath", dest="jsonpath", help="path to conf JSON")
    t.add_argument("--dataset", default="iris")
    t.add_argument("--batch", type=int, default=512)
    t.add_argument("--iterations", type=int, default=150)
    t.add_argument("--seed", type=int, default=0)
    t.add_argument("--out", help="save trained model here")
    t.set_defaults(fn=_cmd_train)

    e = sub.add_parser("evaluate", help="evaluate a saved model")
    e.add_argument("model_path")
    e.add_argument("--dataset", default="iris")
    e.add_argument("--batch", type=int, default=512)
    e.set_defaults(fn=_cmd_evaluate)

    s = sub.add_parser("scaleout", help="multi-process scaleout runtime")
    s.add_argument("-t", "--type", choices=("master", "worker", "kill"),
                   default="master")
    s.add_argument("--state-dir", required=True)
    s.add_argument("--performer",
                   default="deeplearning4j_tpu.parallel.performers:WordCountPerformer")
    s.add_argument("--router", default="wordcount", choices=("wordcount", "average"))
    s.add_argument("--jobs", help="master: text file, one job per line")
    s.add_argument("--workers", type=int, default=2)
    s.add_argument("--worker-id", default="worker-0")
    s.add_argument("--max-wall-s", type=float, default=300.0)
    s.set_defaults(fn=_cmd_scaleout)

    d = sub.add_parser("dryrun", help="multi-chip sharding dryrun")
    d.add_argument("--devices", type=int, default=8)
    d.set_defaults(fn=_cmd_dryrun)

    p = sub.add_parser("provision",
                       help="render or EXECUTE a pod-slice create/bootstrap/"
                            "launch sequence (ClusterSetup parity)")
    p.add_argument("--name", default="dl4j-tpu-slice")
    p.add_argument("--accelerator-type", default="v5litepod-64")
    p.add_argument("--zone", default="us-west4-a")
    p.add_argument("--spot", action="store_true")
    p.add_argument("--repo-url", default="")
    p.add_argument("--train-argv", default="-m deeplearning4j_tpu train")
    p.add_argument("--out", help="write the script here instead of stdout")
    p.add_argument("--apply", action="store_true",
                   help="actually run gcloud (default is dry-run/render)")
    p.add_argument("--dry-run-apply", action="store_true",
                   help="print the apply command sequence without running")
    p.add_argument("--kill", action="store_true",
                   help="tear the slice down instead of bringing it up")
    p.set_defaults(fn=_cmd_provision)

    ap.add_argument("--platform", default=None,
                    help="pin a jax platform (e.g. 'cpu', 'tpu'); default: "
                         "whatever JAX finds")
    args = ap.parse_args(argv)
    if args.platform:
        import jax
        jax.config.update("jax_platforms", args.platform)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
