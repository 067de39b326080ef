"""References, one module each, named for the `correctness.reference` of the
configurations that use it (`names.reference`). A module gives `slice_part`
and `assemble`, and its reference the protocol in `reference`'s docstring.
`counts` and `topn` take theirs from `reference`; a later PR adds its own as
one more file here."""
