"""Frame kinds, one module each, named for the `frame.kind` of the
configurations that use it (`names.kind`). A module gives

    generate(config, seed, data_dir, plan, **kw) -> the reference
    stage_query(frame_name) -> (pql, reference key, op kind)

`dense` and `mixed` take both from a `datagen.Kind`; a later PR adds its own
as one more file here."""
