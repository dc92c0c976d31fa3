"""Per-model precision variants: one AOT-compiled executable per
(variant, shape bucket), sharing a single weight load.

The TVM playbook (PAPERS.md, arxiv 1802.04799) applied to serving:
compile once per (model, dtype, bucket) at registration, dispatch
cheaply at request time. A :class:`VariantSet` owns one replica's
executables, all committed to one device:

- ``fp32`` — the checkpoint as loaded; the gateway's correctness
  reference (batched output is bit-compared against a direct
  ``Predictor.forward`` in tests/test_serving.py).
- ``bf16`` — float params cast to bfloat16 offline, float inputs cast
  at the graph edge, outputs cast back to fp32 (the bench's headline
  inference dtype; on TPU this is the MXU-native path).
- ``int8`` — the full ``contrib/quantization.py`` ``quantize_model``
  KL/naive-calibration flow: BN folding, QuantizeGraph pass, offline
  weight quantization — run ONCE at registration. The *execution
  lowering* is then chosen per backend (the TVM/TensorRT move: one
  quantized model, per-target realizations): ``native`` runs the
  quantized graph itself (int8 MXU compute — the TPU's int8 peak is
  twice its bf16 peak), ``dequant`` serves the weight-only
  realization (the offline-quantized int8 weights folded back through
  their calibrated scales into fp32 constants, original graph
  structure) on backends whose int8 compute is emulated and slower
  than fp32 — XLA CPU prices int8 dots through the scalar emitter at
  3-8x the fp32 GEMM. ``auto`` (default) picks native on tpu,
  dequant elsewhere; both carry the quantization's accuracy effect.

``jax.jit`` caches one executable per input shape, so warmup over the
bucket list is exactly the AOT step: steady-state serving never
retraces (a request batch is always padded to a warmed bucket).
"""
from __future__ import annotations

import logging

import numpy as np

from ..base import MXNetError

VARIANTS = ("fp32", "bf16", "int8")


def default_buckets(max_batch):
    """Powers of two up to ``max_batch`` (which is always included):
    8 -> (1, 2, 4, 8), 12 -> (1, 2, 4, 8, 12). Padding waste is
    bounded at <2x rows while the executable count stays O(log n)."""
    max_batch = int(max_batch)
    if max_batch < 1:
        raise MXNetError("serving: max_batch must be >= 1")
    out = set()
    b = 1
    while b < max_batch:
        out.add(b)
        b *= 2
    out.add(max_batch)
    return tuple(sorted(out))


def pick_bucket(buckets, rows):
    """Smallest bucket >= rows (buckets is the sorted tuple)."""
    for b in buckets:
        if b >= rows:
            return b
    raise MXNetError(
        f"serving: batch of {rows} rows exceeds the largest bucket "
        f"{buckets[-1]} (admission should have rejected it)")


class VariantSet:
    """One replica's compiled forwards: ``run(variant, batch)`` where
    ``batch`` is a numpy array padded to a warmed bucket.

    Parameters mirror :class:`~mxnet_tpu.predictor.Predictor` plus the
    variant list; ``device`` pins params (and therefore compute) to one
    chip — the gateway builds one VariantSet per replica.
    """

    def __init__(self, symbol, arg_params, aux_params, input_name,
                 feature_shape, variants=("fp32",), device=None,
                 calib_data=None, calib_mode="naive",
                 excluded_sym_names=None, input_dtype="float32",
                 int8_lowering="auto", logger=logging):
        self.input_name = input_name
        self.feature_shape = tuple(int(s) for s in feature_shape)
        self.input_dtype = np.dtype(input_dtype)
        self.device = device
        self.variants = tuple(variants)
        self.num_outputs = len(symbol.list_outputs())
        self.int8_lowering = None
        self._fns = {}
        for v in self.variants:
            if v not in VARIANTS:
                raise MXNetError(
                    f"serving: unknown variant {v!r} (have {VARIANTS})")
        if "fp32" in self.variants:
            self._fns["fp32"] = self._compile(symbol, arg_params,
                                              aux_params, cast=None)
        if "bf16" in self.variants:
            self._fns["bf16"] = self._compile(symbol, arg_params,
                                              aux_params, cast="bfloat16")
        if "int8" in self.variants:
            qsym, qarg, qaux = self._quantize(
                symbol, arg_params, aux_params, calib_data, calib_mode,
                excluded_sym_names, logger)
            if int8_lowering == "auto":
                int8_lowering = "native" if self._chip_backend() \
                    else "dequant"
            if int8_lowering == "native":
                self._fns["int8"] = self._compile(qsym, qarg, qaux,
                                                  cast=None)
            elif int8_lowering == "dequant":
                dsym, darg, daux = self._dequant_lowered(
                    symbol, arg_params, aux_params, qarg)
                self._fns["int8"] = self._compile(dsym, darg, daux,
                                                  cast=None)
            else:
                raise MXNetError(
                    f"serving: int8_lowering {int8_lowering!r} not in "
                    "('auto', 'native', 'dequant')")
            self.int8_lowering = int8_lowering

    # -- build ---------------------------------------------------------------
    def _chip_backend(self):
        import jax
        try:
            plat = (self.device.platform if self.device is not None
                    else jax.default_backend())
        except Exception:  # noqa: BLE001 — backend probe must not
            return False   # block registration
        return plat == "tpu"

    def _dequant_lowered(self, symbol, arg_params, aux_params, qarg):
        """Weight-only realization of the quantized model: every param
        the QuantizeGraph pass offline-quantized (``<w>_int8`` +
        calibrated ``_min``/``_max`` scales in ``qarg``) is folded back
        to fp32 through its scale, bound to the BN-folded original
        graph. Same int8 storage/accuracy story, fp32 compute — the
        lowering for backends where emulated int8 loses to fp32."""
        from ..contrib.quantization import (dequantize_offline_params,
                                            fold_batch_norm)

        fsym, farg = fold_batch_norm(symbol, arg_params, aux_params)
        out = dict(farg)
        for base, w in dequantize_offline_params(qarg).items():
            if base in out:
                out[base] = w
        return fsym, out, aux_params

    def _quantize(self, symbol, arg_params, aux_params, calib_data,
                  calib_mode, excluded_sym_names, logger):
        from ..contrib.quantization import quantize_model
        from ..io import NDArrayIter

        it = None
        if calib_mode != "none":
            if calib_data is None:
                raise MXNetError(
                    "serving: int8 variant needs calib_data (numpy "
                    "batch of representative inputs) unless "
                    "calib_mode='none'")
            calib = np.asarray(calib_data, self.input_dtype)
            it = NDArrayIter(data={self.input_name: calib},
                             batch_size=min(len(calib), 8))
        return quantize_model(
            symbol, arg_params, aux_params, calib_mode=calib_mode,
            calib_data=it,
            num_calib_examples=None if it is None else len(calib),
            excluded_sym_names=excluded_sym_names, logger=logger)

    def _compile(self, symbol, arg_params, aux_params, cast=None):
        from ..predictor import compile_symbol_forward

        bindings = dict(arg_params)
        bindings.update(aux_params)
        return compile_symbol_forward(symbol, bindings,
                                      device=self.device, cast=cast)

    # -- dispatch ------------------------------------------------------------
    def run(self, variant, batch):
        """Execute one padded batch; numpy in, list-of-numpy out (the
        ``np.asarray`` is the reply's host transfer — serving replies
        are host-bound by definition)."""
        fn, pvals = self._fns[variant]
        outs = fn(pvals, {self.input_name: np.ascontiguousarray(batch)})
        return [np.asarray(o) for o in outs]

    def warmup(self, buckets):
        """AOT-compile every (variant, bucket) executable by running a
        zeros batch through each — after this, serving never retraces.
        Returns the number of executables warmed."""
        n = 0
        for variant in self.variants:
            for b in buckets:
                zeros = np.zeros((b,) + self.feature_shape,
                                 self.input_dtype)
                self.run(variant, zeros)
                n += 1
        return n
