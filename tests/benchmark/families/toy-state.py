"""A test-only family outside the GQA trunk: one recurrent state a sequence
(``state = decay * state + embed[token]``), no K or V, no pages. It shows that
a configuration file reaches a logits verdict through the family seam with no
edit under ``benchmark/``. The "engine" is the test's stub; its prefill walks
the prompt through the state and each decode step advances it by one token."""

from __future__ import annotations

import dataclasses

import numpy as np

reference = "toy-state-plain"


@dataclasses.dataclass(frozen=True)
class ToyStateConfig:
    name: str
    vocab_size: int
    state_size: int
    decay: float


def model_config(name, config):
    return ToyStateConfig(name, config["vocab_size"], config["state_size"],
                          config["state_decay"])


def engine_logits(engine, check):
    cfg, params = engine.model_config, engine.params

    def step(state, token):
        if engine.step_returns_state_unchanged:      # the broken timed path
            return state
        return np.float32(cfg.decay) * state + params["embed"][token]

    def logits(prompt, forced):
        if len(prompt) + len(forced) > check.tokens:
            raise ValueError("longer than the check's lengths")
        state = np.zeros(cfg.state_size, np.float32)
        for token in prompt:
            state = step(state, token)
        rows = [state @ params["head"]]
        for token in forced:
            state = step(state, token)
            rows.append(state @ params["head"])
        return np.stack(rows).astype(np.float32)

    return logits
