"""Self time of the device operations under the scope ``step_layout`` (the
moves between a step's flat ``[T, ...]`` batch and its padded ``[R, Tc, ...]``
rows, ``StepLayout.flat`` and ``.rows``, and the index arithmetic of
``StepLayout.__init__``: ``paddle_tpu/models/step_layout.py``, one place for
all four serving models) in the traced slice, in ms per engine step in the
slice. Also logs ``bench: layout_by_mixer``: that time by the scope that
encloses ``step_layout`` (``mamba``, ``attn``, ``attn_window``, ``attn_mla``,
``sample``...; ``(top)`` for none)."""
from benchmark import step_budget


def read(run):
    return step_budget.scope_ms_per_step(
        run, step_budget.LAYOUT, "layout_by_mixer",
        step_budget.layout_site_of)
