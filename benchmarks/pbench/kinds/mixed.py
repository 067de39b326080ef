"""bench.build_mixed_holder's recipe: one array or bitmap container a row and
slice in the slice's first block (`datagen._mixed_slice`); writes there."""

from ..datagen import Kind, _mixed_slice, _topn5, block0_candidates

_KIND = Kind(_mixed_slice, block0_candidates, _topn5)
generate, stage_query = _KIND.generate, _KIND.stage_query
