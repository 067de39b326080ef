"""Kinds, one module each, named for the `kind` of the configurations that use
it (`names.kind`: `config["kind"]`, or the `kind` of a configuration's one
frame). A deployment of any number of frames, with PQL the harness has never
sent, is new files only: a configuration (`frames`: a list; `frame` means a
list of that one), its TOML, a kind here, a reference under `refs/`, a traffic
mix, metric files and BENCHMARK.json's entries. A module gives

    generate(config, seed, data_dir, plan, **kw) -> the reference
    stage_query(frame_name) -> (pql, reference key, op kind)

and may give, where the defaults do not fit its deployment,

    bind(op, plan, column) -> schedule.BoundOp
        The PQL of one abstract op (`schedule.AbstractOp`): any PQL, any
        number of requests, the reference's key of the last read, the write
        as (row, column) and the frame written (`BoundOp.frame`: it reaches
        the reference's `judge` as a write's fifth field, and names the
        fragment files the durable look reads). `plan.rows(op)` gives (frame
        name, row id) for each of the op's ranks, `op.frames` the frames the
        traffic file names for the op (`"frames": [...]` on an op: rank i is
        drawn over the i-th named frame's rows; what names past the ranks
        mean, such as a TopN's ranked frame, is this function's to say),
        `plan.config` and `plan.seed` the run's, and `column` the column
        `Plan.assign_columns` picked for an update: from
        `ref.candidates()[frame]`, asked of `ref.can_write(row, column,
        frame)`, where the update names its frame. An op kind that
        `schedule.py` does not know arrives here as written (`op`, `arity`
        ranks, `n`). A read's BoundOp.kind is "count" or "topn" and a
        write's "update": the window's latencies go by them.
    stage_queries(config) -> [(pql, reference key, op kind), ...]
        The queries that stage the deployment's views before the warm-up,
        one request each, all judged.

A kind that gives neither is bound by `schedule.bind`, the default kind's
`bind` (one frame: Count of 2 or all rows, TopN with or without a src row of
the same frame, SetBit + read-back), and staged by its one `stage_query`.
`dense`, `mixed` and `mixed_ingest` take both from a `datagen.Kind`; a later
PR adds its own as one more file here."""
