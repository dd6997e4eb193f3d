"""Decode step: mean over the window's decode steps of the live per-sequence
state rows a step of a STATE-SPACE model read, updated and wrote (the rows of
its decode width that are not on the trash row): ``state.rows_live_mean``'s
reading (``StepEvent.counts.state_rows_live`` from the engine's timeline; its
note is ``state.rows_live``) under a name of its own, because that reader's
list of cells is held to Olmo's. A model without Mamba-2 layers, or a program
whose step records carry no such count, reports nothing."""
from benchmark.harness import layers, ssd_cost


def read(ctx):
    if not ssd_cost.mamba_layers(ctx.model):
        return None
    return layers.load_reader("state.rows_live_mean")(ctx)
