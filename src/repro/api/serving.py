"""ServeSpec — declarative serving of a trained SCALA global model.

The serving counterpart of :class:`repro.api.ExperimentSpec`: a frozen,
JSON-round-trippable description of *what* to serve (arch + federated
training checkpoint) and *how* (slots, paged-cache budget, max length,
sampling). :func:`build_serve` restores the training checkpoint via
:func:`repro.checkpoint.restore`, merges the slot-0 client half with the
server half into the served global model (the same merge
:class:`repro.api.RoundProgram` ``predict`` evaluates — eq. 10's
aggregated client half is broadcast to every slot at round boundaries,
so slot 0 IS the global client half), and returns a
:class:`ServeProgram` wrapping a ready
:class:`repro.serve.ServeEngine`::

    from repro import api

    spec = api.ServeSpec(arch="qwen1.5-0.5b", reduced=True,
                         checkpoint_dir="ckpts/run0", slots=8,
                         max_len=256, pages=64, page_size=16)
    program = api.build_serve(spec)
    out = program.engine.generate(prompts, max_new=32)

With ``checkpoint_dir=""`` the model is freshly initialised from
``seed`` — the smoke/benchmark path.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.base import ModelConfig

ADMISSION_MODES = ("continuous", "static")


@dataclass(frozen=True)
class ServeSpec:
    """Everything one serving deployment needs, declaratively.

    ``pages == 0`` serves from a dense ``slots x max_len`` cache;
    ``pages > 0`` serves from a paged pool of that many pages
    (bit-identical output, memory becomes a pool budget instead of a
    dense per-slot allocation). ``temperature == 0`` is greedy.
    """

    arch: str = "qwen1.5-0.5b"
    reduced: bool = False
    checkpoint_dir: str = ""           # "" = fresh init from `seed`
    checkpoint_step: Optional[int] = None
    slots: int = 4
    max_len: int = 256
    pages: int = 0                     # 0 = dense cache
    page_size: int = 16
    temperature: float = 0.0
    seed: int = 0
    admission: str = "continuous"

    def __post_init__(self):
        cfg = self.model_config()
        if not cfg.is_decoder:
            raise ValueError(f"arch {self.arch!r} is not a decoder; "
                             "ServeSpec serves autoregressive text models")
        if cfg.frontend is not None:
            raise ValueError(f"arch {self.arch!r} has frontend "
                             f"{cfg.frontend!r}; ServeSpec serves text-only "
                             "archs")
        if self.slots < 1:
            raise ValueError(f"slots must be >= 1, got {self.slots}")
        if self.max_len < 2:
            raise ValueError(f"max_len must be >= 2, got {self.max_len}")
        if self.pages < 0:
            raise ValueError(f"pages must be >= 0, got {self.pages}")
        if self.page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {self.page_size}")
        if self.temperature < 0:
            raise ValueError(
                f"temperature must be >= 0, got {self.temperature}")
        if self.admission not in ADMISSION_MODES:
            raise ValueError(f"unknown admission {self.admission!r}; "
                             f"expected {ADMISSION_MODES}")

    def model_config(self) -> ModelConfig:
        cfg = get_config(self.arch)
        return cfg.reduced() if self.reduced else cfg

    # -- lossless serialization -------------------------------------------

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    def to_json(self, **kw) -> str:
        kw.setdefault("indent", 2)
        return json.dumps(self.to_dict(), **kw)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "ServeSpec":
        return cls(**d)

    @classmethod
    def from_json(cls, s: str) -> "ServeSpec":
        return cls.from_dict(json.loads(s))


@dataclass
class ServeProgram:
    """A built serving deployment.

    * ``prefill(tokens)`` — fused prompt absorption: one trunk dispatch
      returning (last-position logits, full decode cache);
    * ``admit(request)`` — prefill a request into a free engine slot
      (False when no capacity);
    * ``step()`` — advance every active slot one token;
    * ``predict(batch)`` — full-sequence logits of the served global
      model (parity surface with ``RoundProgram.predict``);
    * ``engine`` — the underlying :class:`repro.serve.ServeEngine`
      (``serve`` / ``generate`` / ``take_finished``).
    """

    spec: ServeSpec
    cfg: ModelConfig
    params: Any
    engine: Any
    prefill: Callable
    admit: Callable
    step: Callable
    predict: Callable


def restore_global_params(cfg: ModelConfig, directory: str,
                          step: Optional[int] = None):
    """Restore a federated training checkpoint and merge it into the
    served global model.

    ``launch/train.py`` checkpoints ``state.inner.params`` =
    ``{'client': (K, ...) stacked, 'server': ...}``. The stacked client
    count K is inferred from the saved arrays (restore needs an
    exact-shape template), slot 0 of the client half is merged with the
    server half, and the result matches
    :func:`repro.models.transformer.init_params` layout. An unstacked
    (already-merged) checkpoint restores as-is, and a *full-state*
    checkpoint (``Trainer.save`` — the whole ProgramState under
    ``.inner/.params/...`` keys) serves too: the params subtree is
    pulled out by key prefix and everything else ignored.
    """
    from repro import checkpoint
    from repro.models import transformer as T

    shapes = jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), cfg))

    step = checkpoint.latest_step(directory) if step is None else step
    if step is None:
        raise FileNotFoundError(f"no checkpoints in {directory!r}")
    path = os.path.join(directory, f"ckpt_{step:08d}.npz")
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes["client"])
    probe_path, probe = flat[0]
    key = "client/" + "/".join(
        str(getattr(p, "key", getattr(p, "idx", p))) for p in probe_path)
    with np.load(path) as data:
        prefix = "" if key in data.files else ".inner/.params/"
        if prefix + key not in data.files:
            raise ValueError(
                f"checkpoint {path!r} has neither {key!r} nor "
                f"'.inner/.params/{key}' — not a params or full-state "
                f"training checkpoint")
        saved_shape = data[prefix + key].shape

    if saved_shape == probe.shape:
        k_slots = 0                                    # already merged
        client_tpl = jax.tree.map(
            lambda s: np.zeros(s.shape, s.dtype), shapes["client"])
    elif saved_shape[1:] == probe.shape:
        k_slots = saved_shape[0]                       # (K, ...) stacked
        client_tpl = jax.tree.map(
            lambda s: np.zeros((k_slots,) + s.shape, s.dtype),
            shapes["client"])
    else:
        raise ValueError(
            f"checkpoint leaf {key!r} has shape {saved_shape}, expected "
            f"{probe.shape} or (K,)+{probe.shape}")

    template = {
        "client": client_tpl,
        "server": jax.tree.map(lambda s: np.zeros(s.shape, s.dtype),
                               shapes["server"]),
    }
    restored = checkpoint.restore(directory, template, step,
                                  key_prefix=prefix)
    merge = (lambda a: jnp.asarray(a[0])) if k_slots else jnp.asarray
    return {"client": jax.tree.map(merge, restored["client"]),
            "server": jax.tree.map(jnp.asarray, restored["server"])}


def build_serve(spec: ServeSpec) -> ServeProgram:
    """Spec -> running deployment (restore + merge + engine)."""
    from repro.models import transformer as T
    from repro.serve import ServeEngine

    cfg = spec.model_config()
    if spec.checkpoint_dir:
        params = restore_global_params(cfg, spec.checkpoint_dir,
                                       spec.checkpoint_step)
    else:
        params = T.init_params(jax.random.PRNGKey(spec.seed), cfg)

    engine = ServeEngine(
        params, cfg, slots=spec.slots, max_len=spec.max_len,
        pages=spec.pages, page_size=spec.page_size,
        temperature=spec.temperature, seed=spec.seed,
        admission=spec.admission)

    # params enter the jitted fns as arguments, not captured constants
    prefill = functools.partial(jax.jit(
        lambda p, tokens: T.forward_prefill_cached(
            p, {"tokens": tokens}, cfg, spec.max_len)), params)
    predict = functools.partial(jax.jit(
        lambda p, batch: T.forward(p, batch, cfg, remat=False)[0]), params)

    return ServeProgram(spec=spec, cfg=cfg, params=params, engine=engine,
                        prefill=prefill, admit=engine.admit,
                        step=engine.step, predict=predict)
