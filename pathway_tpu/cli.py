"""``pathway_tpu`` command-line launcher.

Parity: reference ``python/pathway/cli.py`` — ``spawn`` (multi-process launcher setting
``PATHWAY_*`` env vars, ``:53-110``), ``spawn-from-env`` (``:284``), record/``replay``
(``:166,252``). Run as ``python -m pathway_tpu.cli <command>``.

Processes launched by ``spawn -n N`` form a cluster: each is told its
``PATHWAY_PROCESS_ID``/``PATHWAY_PROCESSES``/``PATHWAY_FIRST_PORT``, connectors shard
their source partitions (the reference's ``parallel_readers``), and key-partitioned
operators (groupby, join) hash-route every commit's rows to their key's owner process
over the full-mesh TCP exchange (``parallel/cluster.py`` — the reference's
``CommunicationConfig::Cluster``), so global aggregates are exact and each key is
owned by exactly one process. On-device scale-out uses the JAX mesh
(``pathway_tpu.parallel``) within each process.
"""

from __future__ import annotations

import logging
import os
import sys
from typing import NoReturn

import click


def _plural(n: int, singular: str, plural: str) -> str:
    return f"1 {singular}" if n == 1 else f"{n} {plural}"


def _spawn_program(
    *, threads, processes, first_port, program, arguments, env_base,
    max_restarts=0, restart_mode="surgical", scale=None, control_port=None,
    autoscale=None,
):
    """Launch the cluster under the supervisor (``parallel/supervisor.py``):
    child exit codes and per-rank heartbeat status are monitored. On a worker
    crash the supervisor walks the escalation ladder — surgically relaunch
    just the dead rank into the live cluster (persistence on, ``--max-restarts``
    budget, ``--restart-mode surgical``), else restart the whole cluster from
    the persistence journal, else tear everything down with a per-rank
    post-mortem — never a hang."""
    from pathway_tpu.parallel.supervisor import Supervisor

    processes_str = _plural(processes, "process", "processes")
    workers_str = _plural(processes * threads, "total worker", "total workers")
    click.echo(f"Preparing {processes_str} ({workers_str})", err=True)
    scale_plan = None
    if scale:
        # `--scale N`: an elastic membership change to N once the cluster has
        # made its first commits (PATHWAY_SCALE_PLAN carries richer schedules)
        scale_plan = [{"after_commit": 1, "n": scale}]
    supervisor = Supervisor(
        processes=processes,
        threads=threads,
        first_port=first_port,
        program=program,
        arguments=arguments,
        env_base=env_base,
        max_restarts=max_restarts,
        restart_mode=restart_mode,
        scale_plan=scale_plan,
        control_port=control_port,
        autoscale=autoscale,
    )
    sys.exit(supervisor.run())


@click.group
def cli() -> None:
    pass


_SPAWN_SETTINGS = {"allow_interspersed_args": False, "show_default": True}


@cli.command(context_settings=_SPAWN_SETTINGS)
@click.option("-t", "--threads", metavar="N", type=int, default=1, help="number of threads per process")
@click.option("-n", "--processes", metavar="N", type=int, default=1, help="number of processes")
@click.option("--first-port", type=int, metavar="PORT", default=10000, help="first port to use for communication")
@click.option("--record", is_flag=True, help="record data in the input connectors")
@click.option("--record-path", type=str, default="record", help="directory in which record will be saved")
@click.option(
    "--max-restarts",
    type=int,
    metavar="N",
    default=0,
    help="relaunch workers up to N times after a crash, resuming from the "
    "persistence journal (requires the program to run with a persistence "
    "backend; 0 = fail fast with a post-mortem)",
)
@click.option(
    "--restart-mode",
    type=click.Choice(["surgical", "all"], case_sensitive=False),
    default="surgical",
    help="'surgical' relaunches only the dead rank and rejoins it into the "
    "live cluster (survivors hold at an epoch fence; falls back to restarting "
    "the whole cluster when the rejoin itself fails, and finally to a loud "
    "teardown); 'all' always restarts the whole cluster",
)
@click.option(
    "--scale",
    type=int,
    metavar="N",
    default=None,
    help="elastically resize the running cluster to N worker processes once "
    "it is up: the supervisor issues an epoch-fenced MEMBERSHIP_CHANGE — the "
    "workers quiesce at a commit boundary, reshard key ownership, hand off "
    "state through the checkpoint store, and admit joiners / drain leavers "
    "without stopping ingestion (requires persistence; interacts with "
    "--max-restarts: a crash mid-transition recovers by restart-all at "
    "whichever topology the membership manifest committed)",
)
@click.option(
    "--control-port",
    type=int,
    metavar="PORT",
    default=None,
    help="supervisor control endpoint: `echo 'scale N' | nc 127.0.0.1 PORT` "
    "resizes the live cluster; `echo status | nc ...` reports topology + "
    "autoscale-controller state (0 = pick a free port)",
)
@click.option(
    "--autoscale",
    is_flag=True,
    default=False,
    help="closed-loop autoscaler: the supervisor samples the workers' load "
    "signals (ingest rate, shed counters, barrier waits, brownout rung) and "
    "resizes the cluster through the elastic-membership path with no "
    "operator input — damped by hysteresis bands, per-direction cooldowns, "
    "refusal backoff, and a flap lock (PATHWAY_AUTOSCALE_* env knobs tune; "
    "PATHWAY_AUTOSCALE=on enables without this flag)",
)
@click.argument("program")
@click.argument("arguments", nargs=-1)
def spawn(threads, processes, first_port, record, record_path, max_restarts,
          restart_mode, scale, control_port, autoscale, program, arguments):
    env = os.environ.copy()
    if record:
        env["PATHWAY_REPLAY_STORAGE"] = record_path
        env["PATHWAY_SNAPSHOT_ACCESS"] = "record"
        env["PATHWAY_CONTINUE_AFTER_REPLAY"] = "true"
    _spawn_program(
        threads=threads,
        processes=processes,
        first_port=first_port,
        program=program,
        arguments=arguments,
        env_base=env,
        max_restarts=max_restarts,
        restart_mode=restart_mode.lower(),
        scale=scale,
        control_port=control_port,
        autoscale=True if autoscale else None,
    )


@cli.command(context_settings=_SPAWN_SETTINGS)
@click.option("-t", "--threads", metavar="N", type=int, default=1, help="number of threads per process")
@click.option("-n", "--processes", metavar="N", type=int, default=1, help="number of processes")
@click.option("--first-port", type=int, metavar="PORT", default=10000, help="first port to use for communication")
@click.option("--record-path", type=str, default="record", help="directory in which recording is stored")
@click.option("--mode", type=click.Choice(["batch", "speedrun"], case_sensitive=False), help="mode of replaying data")
@click.option(
    "--continue",
    "continue_after_replay",
    is_flag=True,
    help="continue with realtime data from connectors after stored recording is replayed",
)
@click.argument("program")
@click.argument("arguments", nargs=-1)
def replay(threads, processes, first_port, record_path, mode, continue_after_replay, program, arguments):
    env = os.environ.copy()
    env["PATHWAY_REPLAY_STORAGE"] = record_path
    env["PATHWAY_SNAPSHOT_ACCESS"] = "replay"
    if mode:
        env["PATHWAY_PERSISTENCE_MODE"] = mode
    if continue_after_replay:
        env["PATHWAY_CONTINUE_AFTER_REPLAY"] = "true"
    _spawn_program(
        threads=threads,
        processes=processes,
        first_port=first_port,
        program=program,
        arguments=arguments,
        env_base=env,
    )


@cli.command(context_settings=_SPAWN_SETTINGS)
@click.option(
    "--format",
    "fmt",
    type=click.Choice(["text", "json"], case_sensitive=False),
    default="text",
    show_default=True,
    help="diagnostic output format (json is stable for CI parsing)",
)
@click.option(
    "--strict",
    is_flag=True,
    help="treat warnings as errors for the exit code (exit 2 instead of 1)",
)
@click.option(
    "--runtime",
    is_flag=True,
    help="lint the runtime's own modules (PWA101-PWA104 concurrency passes "
    "plus PWA201-PWA205 resource-lifecycle/exception-contract passes: "
    "lock-order cycles, unbounded waits, unlocked shared writes, thread "
    "lifecycle, acquire/release pairing, typed-error swallowing, write-only "
    "state, finally masking, telemetry drift) instead of a user program; "
    "PROGRAM is not required",
)
@click.argument("program", required=False)
@click.argument("arguments", nargs=-1)
def analyze(fmt, strict, runtime, program, arguments):
    """Static graph lint: build PROGRAM's dataflow graph without running it and
    report PWA001-PWA005 diagnostics (or, with ``--runtime``, lint the
    runtime's own source: PWA101-PWA104 concurrency over the threaded modules
    plus PWA201-PWA205 resource-lifecycle/exception contracts).

    Exit-code contract (CI-gateable without parsing text): 0 = clean,
    1 = warnings only (2 with --strict), 2 = errors, 3 = PROGRAM itself crashed
    while building its graph (nothing was analyzed). The program executes up to
    its first ``pw.run`` call; the dataflow itself never starts."""
    import traceback

    from pathway_tpu.analysis import analyze_graph, capture_program_graph

    if runtime:
        if program is not None:
            # a typo'd `analyze --runtime my_graph.py` must not exit 0 with
            # the user's program silently never linted
            raise click.UsageError(
                "--runtime lints the runtime itself and takes no PROGRAM; "
                "run `analyze PROGRAM` separately for the graph lint"
            )
        from pathway_tpu.analysis import analyze_runtime_full

        report = analyze_runtime_full()
        report.emit_telemetry()
        if fmt.lower() == "json":
            click.echo(report.to_json())
        else:
            for diagnostic in report.diagnostics:
                click.echo(diagnostic.format())
            click.echo(report.summary_line())
        sys.exit(report.exit_code(strict=strict))
    if program is None:
        raise click.UsageError("PROGRAM is required unless --runtime is given")
    try:
        graph, persistence = capture_program_graph(program, tuple(arguments))
    except Exception:
        # a crash in the analyzed program must not collide with the 0/1/2
        # diagnostic contract (an uncaught ImportError would exit 1 — the
        # "warnings only, acceptable" code)
        traceback.print_exc()
        click.echo(f"analyze: {program} crashed before its graph was built", err=True)
        sys.exit(3)
    report = analyze_graph(graph, persistence=persistence)
    if fmt.lower() == "json":
        click.echo(report.to_json())
    else:
        for diagnostic in report.diagnostics:
            click.echo(diagnostic.format())
        click.echo(report.summary_line())
    sys.exit(report.exit_code(strict=strict))


@cli.command()
@click.option(
    "--trace-id",
    "trace_id",
    type=str,
    default=None,
    help="render only this trace (16-hex id); default: slowest roots first",
)
@click.option(
    "--limit",
    type=int,
    metavar="N",
    default=5,
    show_default=True,
    help="max traces to render when --trace-id is not given",
)
@click.option(
    "--spans",
    "spans_path",
    type=click.Path(exists=True, dir_okay=False),
    default=None,
    help="with a profiler directory: a trace-rank-N.jsonl of the same run, whose "
    "rest spans split the idle by whether a request was in flight",
)
@click.argument(
    "directory", type=click.Path(exists=True, file_okay=False)
)
def trace(trace_id, limit, spans_path, directory):
    """Merge per-rank trace files into causally-ordered trees.

    DIRECTORY is a supervise/flight dir holding ``trace-rank-N.jsonl``
    files (and, after a crash, ``flight-rank-N.json`` dumps whose trace
    rings are read as partial traces). Wall clocks are aligned to rank 0
    via the heartbeat-estimated offsets each rank recorded at flush, spans
    are joined across REST, encoder, mesh exchange, and replicas, and each
    rendered trace ends with its critical-path one-liner ("commit 4812:
    78% in rank 1 groupby; barrier held 41 ms by rank 3").

    A DIRECTORY that a ``jax.profiler`` session wrote (it holds
    ``plugins/profile/<time>/*.xplane.pb``) is read as a device trace
    instead: for each ``pw.<kind>`` span the host had open, the seconds the
    device sat idle under it. With ``--spans``, that idle splits into what
    fell while a ``rest`` span of that file was open (a request in flight)
    and what fell while none was (nothing to do)."""
    import glob

    from pathway_tpu.engine.tracing import (
        critical_path,
        find_profile,
        format_idle_by_span,
        format_trace_tree,
        idle_by_span,
        load_profile_events,
        load_trace_file,
        merge_trace_files,
    )

    profile = find_profile(directory)
    if profile is not None:
        click.echo(f"profile {profile}")
        spans = load_trace_file(spans_path)[1] if spans_path else None
        for line in format_idle_by_span(idle_by_span(load_profile_events(profile), spans)):
            click.echo(line)
        return
    paths = sorted(glob.glob(os.path.join(directory, "trace-rank-*.jsonl")))
    flights = sorted(glob.glob(os.path.join(directory, "flight-rank-*.json")))
    # replica processes flush into the replicas/ subdir of the supervise dir
    paths += sorted(
        glob.glob(os.path.join(directory, "replicas", "trace-rank-*.jsonl"))
    )
    flights += sorted(
        glob.glob(os.path.join(directory, "replicas", "flight-rank-*.json"))
    )
    if not paths and not flights:
        click.echo(
            f"trace: no trace-rank-*.jsonl or flight-rank-*.json under "
            f"{directory}",
            err=True,
        )
        sys.exit(1)
    merged = merge_trace_files(paths, flights)
    spans = merged["spans"]
    if not spans:
        click.echo(
            "trace: files merged but held no spans (sampling off? try "
            "PATHWAY_TRACE_SAMPLE=1.0)",
            err=True,
        )
        sys.exit(1)
    click.echo(
        f"{len(spans)} spans across ranks {merged['ranks']} "
        f"({len(paths)} trace files, {len(flights)} flight dumps)"
    )
    if trace_id is not None:
        trace_ids = [trace_id]
    else:
        # slowest roots first; traces that arrived only as flight-dump
        # partials (no root survived the crash) render after them
        roots = [s for s in spans if not s.get("parent_id")]
        roots.sort(key=lambda s: s.get("duration_s", 0.0), reverse=True)
        trace_ids = []
        for span in roots:
            if span["trace_id"] not in trace_ids:
                trace_ids.append(span["trace_id"])
        for span in spans:
            if span["trace_id"] not in trace_ids:
                trace_ids.append(span["trace_id"])
        trace_ids = trace_ids[: max(1, limit)]
    for tid in trace_ids:
        lines = format_trace_tree(merged, tid)
        if not lines:
            click.echo(f"trace {tid}: no spans")
            continue
        click.echo(f"trace {tid}:")
        for line in lines:
            click.echo(f"  {line}")
        result = critical_path(merged, tid)
        if result is not None:
            click.echo(f"  critical path: {result['line']}")


@cli.command()
def spawn_from_env():
    cli_spawn_arguments = os.environ.get("PATHWAY_SPAWN_ARGS")
    if cli_spawn_arguments is not None:
        args = ["spawn"] + cli_spawn_arguments.split(" ")
        os.execl(sys.executable, sys.executable, "-m", "pathway_tpu.cli", *args)
    else:
        logging.warning("PATHWAY_SPAWN_ARGS variable is unspecified, exiting...")


def main() -> NoReturn:
    cli.main()


if __name__ == "__main__":
    main()
