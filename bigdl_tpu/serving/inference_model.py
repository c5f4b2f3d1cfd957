"""InferenceModel — thread-safe multi-backend predict holder.

Reference analog (unverified — mount empty): ``scala/orca/.../inference/
InferenceModel.scala`` — holds N model replicas in a blocking queue so many
Flink/HTTP threads can predict concurrently; backends BigDL/OpenVINO/TF/
Torch.  TPU-native: ONE jitted program (XLA queues device work; replicas
buy nothing on a single chip — the pure compiled forward is thread-safe by
construction), and batch-size bucketing so arbitrary request sizes hit a
handful of compiled shapes.  Concurrency capacity lives in
``optim.PredictionService``.
"""

import threading

from typing import Any, Callable, Dict, Optional, Sequence

import numpy as np

import jax


def _bucket(n: int, buckets: Sequence[int]) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


# One MESH-SHARDED program in flight per process: a sharded predict runs
# collectives across every mesh device, and two host threads launching
# such programs concurrently can interleave their collective rendezvous
# in different orders on different devices — a deadlock.  Unsharded
# predicts don't take this lock (pure jitted forwards are thread-safe by
# construction); sharded ones serialize at launch, which matches the
# per-device program queue a real accelerator runtime enforces anyway.
_MESH_EXEC_LOCK = threading.Lock()


class InferenceModel:
    """Wraps (model, variables) — or any callable — for concurrent serving."""

    def __init__(self, model=None, variables: Optional[Dict] = None,
                 predict_fn: Optional[Callable] = None,
                 batch_buckets: Sequence[int] = (1, 4, 16, 64, 256),
                 decode=None, layout=None,
                 weight_quant: Optional[str] = None):
        """``layout``: serve MODEL-SHARDED (docs/parallelism.md
        §Declarative layouts) — a ``parallelism=`` combo string
        (``"tp:8"``, ``"fsdp:2,tp:4"``) or an already-resolved
        :class:`~bigdl_tpu.parallel.ResolvedLayout`.  The per-model
        layout table places every parameter as a ``NamedSharding`` over
        the named mesh, so a checkpoint too big for one chip serves with
        XLA inserting the collectives; :meth:`warmup`'s closed compile
        set (one program per bucket + the decode engine's cache buckets)
        is unchanged — a mixed-size sweep still runs zero unexpected
        recompiles.  The layout is audited at load: silently replicated
        params export ``parallel.layout.replicated_params`` + a flight
        line.

        ``weight_quant="int8"``: serve int8 weights (docs/quantization.md
        §Serving memory hierarchy).  Layered models (Container / keras
        Model) get the module-swap quantization — Linear/Conv2D leaves
        become int8 twins running the int8 MXU matmul.  Raw-
        matrix models (Transformer) get weight-only int8 param storage
        dequantized inside the jitted forward, so HBM at rest drops 4x
        and one chip holds a proportionally bigger checkpoint; the
        decode engine's programs inherit the same stored-int8 params.
        Quantization happens AFTER layout placement, so the int8
        tensors keep the layout's shardings."""
        from bigdl_tpu.runtime.engine import enable_compile_cache

        # a serving process builds no Engine; its bucketed programs (and
        # the decode engine's two per length bucket) are what a restarted
        # worker wants back from the persistent cache
        enable_compile_cache()
        self.layout = None
        if layout is not None:
            from bigdl_tpu.parallel.mesh_policy import (ResolvedLayout,
                                                        mesh_and_layout)

            self.layout = (layout if isinstance(layout, ResolvedLayout)
                           else mesh_and_layout(str(layout)))
        if weight_quant not in (None, "int8"):
            raise ValueError(f"weight_quant {weight_quant!r}: "
                             "None | 'int8'")
        self.weight_quant = weight_quant
        if predict_fn is None:
            if model is None or variables is None:
                raise ValueError("need (model, variables) or predict_fn")

            self._params = variables.get("params", {})
            self._state = variables.get("state", {})
            if self.layout is not None:
                self._params = self.layout.shard_params(model,
                                                        self._params)
            deq = None
            if weight_quant == "int8":
                from bigdl_tpu.nn import quantized as nq
                from bigdl_tpu.nn.module import Container

                if isinstance(model, Container) or nq._is_keras_model(
                        model):
                    # module swap: Linear/Conv2D leaves become int8
                    # twins on the int8 MXU matmul path
                    model, v = nq.quantize(
                        model, {"params": self._params,
                                "state": self._state})
                    self._params = v.get("params", {})
                    self._state = v.get("state", {})
                else:
                    # raw-matrix models (Transformer): weight-only int8
                    # storage, dequantized inside the jitted forward
                    self._params = nq.quantize_params(self._params)
                    deq = nq.dequantize_params

            def raw(params, state, x):
                if deq is not None:
                    params = deq(params)
                out, _ = model.forward(params, state, x, training=False)
                return out

            self._jit = jax.jit(raw)
            self._custom = None
        else:
            if self.layout is not None:
                raise ValueError("layout= applies to (model, variables) "
                                 "serving, not a custom predict_fn")
            if weight_quant is not None:
                raise ValueError("weight_quant= applies to (model, "
                                 "variables) serving, not a custom "
                                 "predict_fn")
            self._custom = predict_fn
        self.buckets = tuple(sorted(batch_buckets))
        # autoregressive decode path (docs/serving.md §Autoregressive
        # decode): a DecodeConfig attaches the paged-KV continuous
        # decode engine; generate()/generate_stream() and the server's
        # generate requests route through it.  A DecodeConfig with
        # speculative=SpecConfig(...) additionally builds the weight-
        # shared block-sparse draft twin from this model's (already
        # laid-out, already-quantized) params at load time
        # (docs/serving.md §Speculative decoding)
        self.decode_engine = None
        if decode is not None:
            from bigdl_tpu.serving.decode_engine import (DecodeEngine,
                                                         LMAdapter)

            if model is None or getattr(model, "mode", None) != "lm":
                raise ValueError(
                    "decode= needs an LM-mode Transformer (model, "
                    "variables); for translation models use "
                    "Seq2SeqService(continuous=True)")
            # the adapter receives the already-quantized tree under
            # weight_quant="int8" (quantize_params is idempotent) — the
            # engine's traced programs dequantize at each weight read
            adapter = LMAdapter(model, self._params, cap=decode.cap,
                                weight_quant=self.weight_quant)
            self.decode_engine = DecodeEngine(adapter, decode)
        # no lock: the jitted forward is pure and JAX dispatch is
        # thread-safe, so concurrent predicts are safe by construction
        # (the reference needs its replica queue only because its layers
        # carry mutable output/gradInput state).  Concurrency CAPACITY is
        # the caller's concern — see optim.PredictionService.

    @staticmethod
    def load(path: str, model) -> "InferenceModel":
        """Load from the durable model format (``doLoadBigDL`` analog)."""
        from bigdl_tpu.utils.serializer import load_model

        return InferenceModel(model, load_model(path))

    @staticmethod
    def load_tf(path: str, **kwargs) -> "InferenceModel":
        """Serve a frozen TF GraphDef (``doLoadTF``/TFNet analog — no
        libtensorflow: the graph becomes catalog modules via utils.tfio)."""
        from bigdl_tpu.utils.tfio import load_tf_graph

        model, variables = load_tf_graph(path, **kwargs)
        return InferenceModel(model, variables)

    @staticmethod
    def load_caffe(path: str, **kwargs) -> "InferenceModel":
        """Serve a Caffe NetParameter (``doLoadCaffe`` analog); NHWC inputs
        per the utils.caffe import conversion."""
        from bigdl_tpu.utils.caffe import load_caffe

        model, variables = load_caffe(path, **kwargs)
        return InferenceModel(model, variables)

    def predict(self, x: np.ndarray) -> np.ndarray:
        x = np.asarray(x)
        if self._custom is not None:
            return np.asarray(self._custom(x))
        cap = self.buckets[-1]
        if x.shape[0] > cap:
            # chunk instead of running an unpadded tail shape: the set of
            # compiled programs stays CLOSED (one per bucket), so a burst
            # bigger than the largest bucket cannot trigger a fresh XLA
            # compile mid-traffic (the recompile-sentinel guarantee)
            return np.concatenate(
                [self._predict_bucketed(x[i:i + cap])
                 for i in range(0, x.shape[0], cap)], axis=0)
        return self._predict_bucketed(x)

    def _predict_bucketed(self, x: np.ndarray) -> np.ndarray:
        n = x.shape[0]
        b = _bucket(n, self.buckets)
        if n < b:  # pad to the bucket so XLA reuses the compiled program
            pad = np.repeat(x[-1:], b - n, axis=0)
            x = np.concatenate([x, pad], axis=0)
        if self.layout is not None:
            with _MESH_EXEC_LOCK:
                out = self._jit(self._params, self._state, x)
                return np.asarray(out)[:n]
        out = self._jit(self._params, self._state, x)
        return np.asarray(out)[:n]

    # -- autoregressive decode (docs/serving.md §Autoregressive decode) -----
    def generate(self, prompts, max_new_tokens: Optional[int] = None,
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 1.0, seeds=None,
                 deadline_s: Optional[float] = None):
        """Generate continuations for ``prompts`` (a list of int token
        sequences) through the continuous decode engine — requests
        share the slot pool with any concurrently streaming traffic.
        Greedy by default; ``temperature/top_k/top_p`` sample with the
        per-request ``seeds`` (defaults to the prompt index).  Returns
        a list of generated-token arrays (EOS included when hit)."""
        import math as _math
        import time as _time

        from bigdl_tpu.serving.decode_engine import DecodeRequest

        if self.decode_engine is None:
            raise ValueError("this InferenceModel has no decode engine; "
                             "construct it with decode=DecodeConfig(...)")
        deadline_t = (_time.time() + deadline_s if deadline_s is not None
                      else _math.inf)
        reqs = []
        for i, p in enumerate(prompts):
            reqs.append(self.decode_engine.submit(DecodeRequest(
                tokens=np.asarray(p, np.int32),
                max_new_tokens=max_new_tokens, temperature=temperature,
                top_k=top_k, top_p=top_p,
                seed=int(seeds[i]) if seeds is not None else i,
                deadline_t=deadline_t)))
        return [r.wait(timeout=300.0).tokens for r in reqs]

    def generate_stream(self, prompt, max_new_tokens: Optional[int] = None,
                        temperature: float = 0.0, top_k: int = 0,
                        top_p: float = 1.0, seed: int = 0,
                        deadline_s: Optional[float] = None):
        """Streaming generate: yields token ids as they decode.  One
        request; keyword args as :meth:`generate`."""
        import math as _math
        import queue as _queue
        import time as _time

        from bigdl_tpu.serving.decode_engine import DecodeRequest

        if self.decode_engine is None:
            raise ValueError("this InferenceModel has no decode engine; "
                             "construct it with decode=DecodeConfig(...)")
        q: _queue.Queue = _queue.Queue()
        done = object()
        req = DecodeRequest(
            tokens=np.asarray(prompt, np.int32),
            max_new_tokens=max_new_tokens, temperature=temperature,
            top_k=top_k, top_p=top_p, seed=seed,
            deadline_t=(_time.time() + deadline_s
                        if deadline_s is not None else _math.inf),
            on_token=lambda rid, tok, idx: q.put(tok),
            on_done=lambda r: q.put(done))
        self.decode_engine.submit(req)
        while True:
            item = q.get()
            if item is done:
                break
            yield item
        if req.error is not None:
            raise req.error

    def warmup(self, sample: np.ndarray) -> "InferenceModel":
        """Compile every bucket's program BEFORE traffic: one predict per
        bucket from ``sample`` (a single example, with or without a batch
        dim), inside an :func:`~bigdl_tpu.obs.attr.expected_compile`
        region so the recompile sentinel stays quiet.  After this, a
        mixed-size request sweep runs with zero XLA compiles."""
        if self._custom is not None:
            return self
        from bigdl_tpu.obs.attr import expected_compile

        row = np.asarray(sample)
        if row.ndim >= 2:
            row = row[:1]
        else:
            row = row[None]
        with expected_compile():
            for b in self.buckets:
                self._predict_bucketed(np.repeat(row, b, axis=0))
        if self.decode_engine is not None:
            self.decode_engine.warmup()
        return self
