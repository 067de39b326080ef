"""Whole rows of random words, every container of every slice held
(`datagen._dense_slice`); writes anywhere in the index."""

from ..datagen import Kind, _count_row0, _dense_slice, write_candidates

_KIND = Kind(_dense_slice, write_candidates, _count_row0)
generate, stage_query = _KIND.generate, _KIND.stage_query
