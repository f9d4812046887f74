"""Pipeline launcher: ``python -m keystone_tpu <pipeline> [args...]``.

The successor of the reference's ``bin/run-pipeline.sh <Class> args``
(SURVEY.md layer 8): dispatches to a model's ``main`` by short name or by
reference-style class name, so existing KeystoneML invocations map 1:1.
"""

from __future__ import annotations

import sys

# short name → (module, reference class name)
PIPELINES = {
    "mnist-random-fft": (
        "keystone_tpu.models.mnist_random_fft",
        "pipelines.images.mnist.MnistRandomFFT",
    ),
    "cifar-linear-pixels": (
        "keystone_tpu.models.cifar_linear_pixels",
        "pipelines.images.cifar.LinearPixels",
    ),
    "cifar-random-patch": (
        "keystone_tpu.models.cifar_random_patch",
        "pipelines.images.cifar.RandomPatchCifar",
    ),
    "cifar-random": (
        "keystone_tpu.models.cifar_random",
        "pipelines.images.cifar.RandomCifar",
    ),
    "voc-sift-fisher": (
        "keystone_tpu.models.voc_sift_fisher",
        "pipelines.images.voc.VOCSIFTFisher",
    ),
    "imagenet-sift-lcs-fv": (
        "keystone_tpu.models.imagenet_sift_lcs_fv",
        "pipelines.images.imagenet.ImageNetSiftLcsFV",
    ),
    "timit": (
        "keystone_tpu.models.timit_pipeline",
        "pipelines.speech.TimitPipeline",
    ),
    "newsgroups": (
        "keystone_tpu.models.newsgroups_pipeline",
        "pipelines.text.NewsgroupsPipeline",
    ),
    "stupid-backoff": (
        "keystone_tpu.models.stupid_backoff_pipeline",
        "pipelines.nlp.StupidBackoffPipeline",
    ),
    "vit-ridge": ("keystone_tpu.models.vit_ridge", None),
    "lm-transformer": ("keystone_tpu.models.lm_transformer", None),
    # the same entry by its short name: `lm --config laguna_xs2`
    "lm": ("keystone_tpu.models.lm_transformer", None),
}

# non-pipeline subcommands: short name → module whose ``main(argv)`` runs
COMMANDS = {
    "observe": "keystone_tpu.observe.report",
    "faults": "keystone_tpu.resilience.faults",
    "plan": "keystone_tpu.plan.cli",
    "supervise": "keystone_tpu.resilience.supervisor",
    "serve": "keystone_tpu.serve.server",
    "fleet": "keystone_tpu.serve.fleet",
    "refit": "keystone_tpu.learn.refit",
    "chaos": "keystone_tpu.resilience.chaos",
}


def main(argv: list[str] | None = None) -> None:
    # the platform rule (core/runtime.py): explicit JAX_PLATFORMS obeyed,
    # unset means TPU. Applied before any subcommand so the processes a
    # command spawns (fleet replicas, supervised workers) inherit it;
    # commands that never compute (the fleet router, observe, chaos)
    # never bring a backend up — a chip belongs to one process
    from keystone_tpu.core import runtime

    runtime.select_platform()
    argv = list(sys.argv[1:] if argv is None else argv)
    multihost = "--multihost" in argv
    if multihost:
        argv.remove("--multihost")
    profile_dir = None
    if "--profile" in argv:
        i = argv.index("--profile")
        if i + 1 >= len(argv):
            raise SystemExit("--profile needs a trace directory argument")
        profile_dir = argv[i + 1]
        del argv[i : i + 2]
    observe_dir = None
    if "--observe" in argv:
        i = argv.index("--observe")
        if i + 1 >= len(argv):
            raise SystemExit("--observe needs an output directory argument")
        observe_dir = argv[i + 1]
        del argv[i : i + 2]
    if not argv or argv[0] in ("-h", "--help"):
        names = "\n  ".join(sorted(PIPELINES))
        commands = "\n  ".join(sorted(COMMANDS))
        raise SystemExit(
            f"usage: python -m keystone_tpu [--multihost] "
            f"[--profile DIR] [--observe DIR] <pipeline> [args...]\n"
            f"pipelines:\n  {names}\n"
            f"commands:\n  {commands}\n"
            f"(reference class names like pipelines.images.mnist.MnistRandomFFT"
            f" are also accepted; --multihost joins this process into the\n"
            f" jax.distributed runtime before dispatch — run the same command"
            f" on every host; --observe DIR writes a structured per-node\n"
            f" event log there, rendered by `observe <dir>`, tailed live by\n"
            f" `observe top <dir>` (a base dir tails EVERY run dir — the\n"
            f" fleet view), and compared across runs by\n"
            f" `observe diff <dirA> <dirB>`; --profile DIR writes a\n"
            f" jax.profiler trace of the run there, and `observe idle DIR`\n"
            f" puts each idle gap of the device down to the host span that\n"
            f" was open over it (spans are recorded under either --observe\n"
            f" or --profile); `observe collect <out>` runs\n"
            f" the fleet collector (scrapes every /metrics, tails run dirs,\n"
            f" evaluates SLO burn rates), `observe slo <out>` renders its\n"
            f" verdicts + exemplars, and `observe serve <out> --port N` is\n"
            f" the live fleet dashboard with federation /metrics;\n"
            f" `faults --list`\n"
            f" prints the KEYSTONE_FAULTS injection sites; `plan <model>`\n"
            f" prints the cost-based planner's chosen plan without executing\n"
            f" (`--learned` shows the KEYSTONE_PLAN_STORE record instead);\n"
            f" `supervise -- CMD` relaunches a multihost job on host loss —\n"
            f" see `supervise --help`; `serve <model> [--port N]` serves a\n"
            f" fitted pipeline or LM over HTTP/JSON — see `serve --help`;\n"
            f" `fleet <model>` runs a health-aware router over N replica\n"
            f" servers with failover and `fleet restart` rolling restarts —\n"
            f" see `fleet --help`;\n"
            f" `refit <state> --watch DIR` folds live labeled chunks into\n"
            f" streaming-fit state and republishes versioned models — see\n"
            f" `refit --help`;\n"
            f" `chaos run <campaign.json>` executes a composed multi-fault\n"
            f" game day against a fleet/train/refit workload and verdicts\n"
            f" its declarative invariants from the observe substrate —\n"
            f" `chaos list` shows the canned campaigns, see `chaos --help`)"
        )
    if argv[0] in COMMANDS:
        import importlib

        return importlib.import_module(COMMANDS[argv[0]]).main(argv[1:])
    if multihost:
        from keystone_tpu.parallel import multihost as mh
        from keystone_tpu.resilience import cluster as _cluster

        mh.initialize()
        # membership heartbeats + failure detection for the whole run:
        # a lost host becomes a clean EXIT_HOST_LOST exit (below) that
        # `python -m keystone_tpu supervise` relaunches, instead of a
        # silent collective hang
        _cluster.start_monitor()
    name, rest = argv[0], argv[1:]
    target = None
    if name in PIPELINES:
        target = PIPELINES[name][0]
    else:
        for _short, (mod, ref) in PIPELINES.items():
            if ref == name:
                target = mod
                break
    if target is None:
        raise SystemExit(f"unknown pipeline {name!r}; run with --help for a list")
    import importlib

    entry = importlib.import_module(target).main
    # backend up (after any jax.distributed join) and named in the log
    # before the pipeline runs; no device of the selected platform
    # raises here with the backend's own error
    runtime.init_backend()

    def dispatch():
        if profile_dir is not None:
            from keystone_tpu.core.profiling import trace

            with trace(profile_dir):
                return entry(rest)
        return entry(rest)

    if observe_dir is None:
        import os

        observe_dir = os.environ.get("KEYSTONE_OBSERVE_DIR") or None
    def rollup():
        # multihost metrics roll-up: every host calls it (collective
        # barrier); host 0 merges cluster totals into the run dir so the
        # report isn't host-0-only. Never fatal. Skipped after a host
        # loss — the roll-up barrier would only time out against the
        # dead peer.
        if not multihost:
            return
        from keystone_tpu.resilience import cluster as _cl

        if _cl.check_lost() is not None:
            return
        try:
            from keystone_tpu.observe import events as _events
            from keystone_tpu.parallel import multihost as mh_roll

            log = _events.active()
            mh_roll.rollup_metrics(log.run_dir if log else None)
        except Exception as e:  # noqa: BLE001
            import sys as _sys

            print(
                f"# multihost metrics roll-up failed: {e!r}",
                file=_sys.stderr,
            )

    try:
        if observe_dir is not None:
            # scoped run: the launcher brackets the whole pipeline with
            # run_start/run_end so the report knows total wall and status
            from keystone_tpu.observe import events

            with events.run(observe_dir, pipeline=name, argv=rest):
                dispatch()
                rollup()
        else:
            dispatch()
            rollup()
    except Exception as e:
        if multihost:
            from keystone_tpu.resilience import cluster as _cl

            if isinstance(e, _cl.ClusterError):
                # the supervisor's exit-code protocol: host loss is a
                # re-mesh request, not a crash
                print(f"# host loss: {e}", file=sys.stderr)
                raise SystemExit(_cl.EXIT_HOST_LOST) from e
        raise
    finally:
        if multihost:
            from keystone_tpu.resilience import cluster as _cl

            _cl.stop_monitor()


if __name__ == "__main__":
    main()
