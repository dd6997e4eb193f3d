"""The plain reference of the test-only ``toy-state`` family: the state at
position p in closed form, no recurrence carried."""

import numpy as np


def forward(params, config, tokens, positions):
    embedded = params["embed"][np.asarray(tokens)].astype(np.float64)
    states = [sum(config.decay ** (p - s) * embedded[s] for s in range(p + 1))
              for p in positions]
    return np.stack(states) @ params["head"].astype(np.float64), None
