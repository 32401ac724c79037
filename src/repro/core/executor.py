"""Real executor: run a workflow DAG as actual JAX computation on local devices.

The simulator proves scheduling at cluster scale; this module proves the
*plumbing* end-to-end — every agent invocation is a real JAX program over
real arrays, using the model zoo's reduced configs on whatever device JAX
finds (the Pallas kernels on a TPU, their jnp twins elsewhere):

  frame_extract   strided frame sampling (jnp slicing/pooling)
  speech_to_text  seamless-m4t (reduced) enc-dec generate over audio features
  object_detect   CLIP-style dual-encoder cosine scoring of frames vs labels
  summarize       zoo LM (reduced) prefill+decode over a context prompt
  embed           mean-pooled embedding-table vectors into an in-memory DB
  qa              nearest-vector retrieval + LM generate

Outputs flow along the DAG's dataflow edges, so a mis-wired dependency fails
loudly (missing input type), and the Murakkab/baseline paths can be compared
for *output equality* (same seeds -> same tokens), mirroring the paper's
"execution output and accuracy are the same in all comparisons".
"""
from __future__ import annotations

import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.registry import get_config
from ..models.model_zoo import build_model
from ..runtime.serve import ServeSession, ServeOptions
from .agents import AgentLibrary
from .dag import DAG
from .scheduler import ExecutionPlan


@dataclass
class Media:
    """Synthetic decoded video: frames + audio features per scene."""

    name: str
    frames: jax.Array          # (scenes, fps, 32, 32, 3) uint8-ish floats
    audio: jax.Array           # (scenes, T, d_audio) float32

    @classmethod
    def synthesize(cls, name: str, scenes: int = 4, fps: int = 10,
                   seed: int = 0) -> "Media":
        """Deterministic random media standing in for a decoded video."""
        k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
        frames = jax.random.uniform(k1, (scenes, fps, 32, 32, 3))
        audio = jax.random.normal(k2, (scenes, 64, 80))
        return cls(name, frames, audio)


_LABELS = ["cat", "car", "tree", "person", "dog", "road", "sky", "wheel",
           "helmet", "grass", "sign", "flag", "track", "ball", "house",
           "water"]


class RealExecutor:
    """Executes DAG nodes with real reduced-config JAX models."""

    def __init__(self, library: AgentLibrary, seed: int = 0,
                 default_arch: str = "deepseek-7b"):
        self.library = library
        self.seed = seed
        self.default_arch = default_arch
        self._sessions: dict[str, ServeSession] = {}
        self._vector_db: list[tuple[np.ndarray, jax.Array]] = []

    # -- model sessions ----------------------------------------------------------
    def session(self, arch: str) -> ServeSession:
        """Lazily-built serving session for one reduced zoo config."""
        if arch not in self._sessions:
            cfg = get_config(arch, reduced=True)
            model = build_model(cfg)
            params = model.init(jax.random.PRNGKey(self.seed))
            self._sessions[arch] = ServeSession(model, params,
                                                opts=ServeOptions())
        return self._sessions[arch]

    # -- agent implementations -----------------------------------------------------
    def frame_extract(self, media: list[Media], args: dict) -> jax.Array:
        """Strided frame sampling over all scenes."""
        stride = max(int(args.get("sampling_rate", 15)) // 15, 1)
        out = jnp.concatenate([m.frames[:, ::stride] for m in media], 0)
        return out                                  # (scenes, fps', 32, 32, 3)

    def speech_to_text(self, media: list[Media], arch: str | None) \
            -> jax.Array:
        """Transcribe audio features with a (reduced) enc-dec or LM."""
        arch = arch or "seamless-m4t-large-v2"
        sess = self.session(arch)
        cfg = sess.model.cfg
        audio = jnp.concatenate([m.audio for m in media], 0)  # (S, T, 80)
        B, T, _ = audio.shape
        if cfg.family == "encdec":
            # project audio features to d_model "frames" (stub frontend)
            d = cfg.d_model
            reps = -(-d // audio.shape[-1])
            frames = jnp.tile(audio, (1, 1, reps))[..., :d].astype(jnp.bfloat16)
            bos = jnp.zeros((B, 1), jnp.int32)
            toks = sess.generate(bos, max_new_tokens=8,
                                 extras={"frames": frames})
        else:
            bos = (jnp.abs(audio[:, 0, :8]) * 100).astype(jnp.int32) % \
                sess.model.cfg.vocab_size
            toks = sess.generate(bos, max_new_tokens=8)
        return toks                                 # (scenes, 8) transcript ids

    def object_detect(self, frames: jax.Array, arch: str | None) -> jax.Array:
        """CLIP-style: random-projection image/text encoders, cosine top-1."""
        S, F = frames.shape[:2]
        key = jax.random.PRNGKey(self.seed + 1)
        k_img, k_txt = jax.random.split(key)
        d = 64
        img_proj = jax.random.normal(k_img, (32 * 32 * 3, d)) / 55.4
        txt_emb = jax.random.normal(k_txt, (len(_LABELS), d))
        img = frames.reshape(S, F, -1) @ img_proj                  # (S,F,d)
        img = img / jnp.linalg.norm(img, axis=-1, keepdims=True)
        txt = txt_emb / jnp.linalg.norm(txt_emb, axis=-1, keepdims=True)
        scores = jnp.einsum("sfd,ld->sfl", img, txt)
        return jnp.argmax(scores, -1)               # (scenes, frames) label ids

    def summarize(self, frames, objects, transcript, arch: str | None) \
            -> jax.Array:
        """LM generate over a deterministic per-scene context prompt."""
        arch = arch or self.default_arch
        sess = self.session(arch)
        V = sess.model.cfg.vocab_size
        S = objects.shape[0]
        # build a deterministic "prompt" per scene from the gathered context
        ctx = jnp.concatenate([
            objects[:, :8].astype(jnp.int32) % V,
            transcript[:, :8].astype(jnp.int32) % V,
            (jnp.mean(frames.reshape(S, -1), -1, keepdims=True) * 1000
             ).astype(jnp.int32) % V,
        ], axis=1)
        return sess.generate(ctx, max_new_tokens=8)  # (scenes, 8) summaries

    def embed(self, summaries: jax.Array, arch: str | None) -> jax.Array:
        """Mean-pooled embedding vectors, inserted into the in-memory DB."""
        arch = arch or self.default_arch
        sess = self.session(arch)
        emb = sess.params["embed"]                   # (V, d)
        vecs = jnp.take(emb, summaries % emb.shape[0], axis=0).mean(1)
        for i in range(vecs.shape[0]):
            self._vector_db.append((np.asarray(vecs[i], np.float32),
                                    summaries[i]))
        return vecs                                  # (scenes, d)

    def qa(self, vectors: jax.Array, question: str, arch: str | None) \
            -> jax.Array:
        """Nearest-vector retrieval + LM generate over the question."""
        arch = arch or self.default_arch
        sess = self.session(arch)
        V = sess.model.cfg.vocab_size
        q = jnp.asarray([ord(c) % V for c in question[:16]], jnp.int32)[None]
        if self._vector_db:
            qv = np.asarray(jnp.take(sess.params["embed"], q[0],
                                     axis=0).mean(0), np.float32)
            sims = [float(qv @ v) for v, _ in self._vector_db]
            best = self._vector_db[int(np.argmax(sims))][1][None]
            q = jnp.concatenate([q, best.astype(jnp.int32) % V], 1)
        return sess.generate(q, max_new_tokens=8)

    # -- DAG walk -----------------------------------------------------------------
    def run(self, dag: DAG, plan: ExecutionPlan | None, media: list[Media],
            question: str = "") -> dict:
        """Execute in topological order; returns {task_id: output} + timings."""
        outputs: dict[str, object] = {}
        by_type: dict[str, object] = {}
        timings: dict[str, float] = {}
        for tid in dag.topo_order:
            node = dag.nodes[tid]
            impl_name = plan[tid].impl if plan else None
            arch = (self.library.impls[impl_name].arch
                    if impl_name and impl_name in self.library.impls else None)
            t0 = time.perf_counter()
            if node.agent == "frame_extract":
                out = self.frame_extract(media, node.args)
            elif node.agent == "speech_to_text":
                out = self.speech_to_text(media, arch)
            elif node.agent == "object_detect":
                out = self.object_detect(by_type["frames"], arch)
            elif node.agent == "summarize":
                out = self.summarize(by_type["frames"], by_type["objects"],
                                     by_type["transcript"], arch)
            elif node.agent == "embed":
                out = self.embed(by_type["summary"], arch)
            elif node.agent == "qa":
                out = self.qa(by_type.get("vectors"), question or
                              node.args.get("question", ""), arch)
            else:
                raise ValueError(f"real executor: unknown agent {node.agent}")
            jax.block_until_ready(out)
            timings[tid] = time.perf_counter() - t0
            outputs[tid] = out
            by_type[self.library.interfaces[node.agent].produces] = out
        outputs["_timings"] = timings
        return outputs
