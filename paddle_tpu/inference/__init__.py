"""paddle.inference equivalent.

Reference parity: paddle/fluid/inference/api/analysis_predictor.h:82
AnalysisPredictor + paddle_infer Python API (Config, create_predictor,
zero-copy input/output handles). TPU-native: a saved model is a serialized
StableHLO program + params (jit.save format); the predictor executes the
deserialized XLA executable — the analysis pass pipeline (fusions, memory
optimize) is XLA compilation itself.
"""
import numpy as np

from ..core.tensor import Tensor
from ..jit.save_load import load as _jit_load


_warned_knobs = set()


def _warn_unsupported(knob, equivalent):
    """One warning per unsupported Config knob per process — these are
    accepted for source compat but MUST not be silent no-ops (a user
    flipping enable_use_gpu deserves to learn what actually runs)."""
    if knob in _warned_knobs:
        return
    _warned_knobs.add(knob)
    import warnings
    warnings.warn(
        f"paddle.inference.Config.{knob} has no effect on TPU: "
        f"{equivalent}", UserWarning, stacklevel=3)


class Config:
    """Reference: AnalysisConfig. Model path + execution knobs; GPU/TRT
    options accepted for source compat but warn once (XLA owns
    optimization; the TPU equivalent is named in the warning)."""

    def __init__(self, prog_file=None, params_file=None):
        if prog_file is not None and prog_file.endswith(".pdmodel"):
            prog_file = prog_file[:-len(".pdmodel")]
        self._model_prefix = prog_file
        self._enable_memory_optim = True

    def set_prog_file(self, path):
        self._model_prefix = path[:-len(".pdmodel")] \
            if path.endswith(".pdmodel") else path

    def model_dir(self):
        return self._model_prefix

    def enable_use_gpu(self, *a, **k):
        _warn_unsupported(
            "enable_use_gpu",
            "the predictor runs on the TPU (or CPU) jax backend; "
            "device selection follows JAX_PLATFORMS")

    def enable_memory_optim(self, flag=True):
        self._enable_memory_optim = flag

    def switch_ir_optim(self, flag=True):
        if not flag:
            _warn_unsupported(
                "switch_ir_optim(False)",
                "XLA compilation IS the IR-optimization pipeline here "
                "and cannot be disabled")

    def enable_tensorrt_engine(self, *a, **k):
        _warn_unsupported(
            "enable_tensorrt_engine",
            "XLA is the execution engine; for int8 use "
            "paddle.quantization PTQ/QAT which runs W8A8 on the int8 "
            "MXU")

    def disable_glog_info(self):
        pass  # genuinely a logging knob; nothing to warn about


class _IOHandle:
    def __init__(self, predictor, name, is_input):
        self._p = predictor
        self.name = name
        self._is_input = is_input

    def reshape(self, shape):
        pass

    def copy_from_cpu(self, arr):
        self._p._inputs[self.name] = np.asarray(arr)

    def copy_to_cpu(self):
        return self._p._outputs[self.name]

    def share_external_data(self, arr):
        self.copy_from_cpu(arr)


class Predictor:
    def __init__(self, config):
        self._layer = _jit_load(config.model_dir())
        n_in = 0
        import pickle
        with open(config.model_dir() + ".pdmeta", "rb") as f:
            meta = pickle.load(f)
        self._input_names = [f"x{i}" for i in range(meta["num_inputs"])]
        self._inputs = {}
        self._outputs = {}
        self._output_names = []
        # memory_optim (reference: AnalysisConfig::EnableMemoryOptim —
        # reuse/free buffers between runs): drop staged host inputs and
        # stale outputs after each run instead of keeping them resident
        self._memory_optim = bool(getattr(config,
                                          "_enable_memory_optim", True))

    def get_input_names(self):
        return list(self._input_names)

    def get_input_handle(self, name):
        return _IOHandle(self, name, True)

    def run(self, inputs=None):
        if inputs is not None:  # direct call style
            arrs = [np.asarray(a) for a in inputs]
        else:
            arrs = [self._inputs[n] for n in self._input_names]
        if self._memory_optim:
            self._outputs = {}          # free previous run's outputs
        out = self._layer(*[Tensor(a) for a in arrs])
        outs = out if isinstance(out, tuple) else (out,)
        self._output_names = [f"out{i}" for i in range(len(outs))]
        self._outputs = {n: o.numpy() for n, o in
                         zip(self._output_names, outs)}
        # staged inputs stay resident (reference AnalysisPredictor
        # semantics: run() is repeatable without re-copying inputs)
        if inputs is not None:
            return [self._outputs[n] for n in self._output_names]
        return True

    def get_output_names(self):
        return list(self._output_names) or ["out0"]

    def get_output_handle(self, name):
        return _IOHandle(self, name, False)


def create_predictor(config):
    return Predictor(config)


PrecisionType = type("PrecisionType", (), {"Float32": 0, "Half": 1,
                                           "Bfloat16": 2, "Int8": 3})
PlaceType = type("PlaceType", (), {"CPU": 0, "GPU": 1, "XPU": 2, "TPU": 4})


class DataType:  # reference: paddle_infer.DataType enum
    FLOAT32 = 0
    INT64 = 1
    INT32 = 2
    UINT8 = 3
    INT8 = 4
    FLOAT16 = 5
    BFLOAT16 = 6


_DTYPE_BYTES = {DataType.FLOAT32: 4, DataType.INT64: 8,
                DataType.INT32: 4, DataType.UINT8: 1, DataType.INT8: 1,
                DataType.FLOAT16: 2, DataType.BFLOAT16: 2}


def get_num_bytes_of_data_type(dtype):
    return _DTYPE_BYTES[dtype]


def get_version():
    from .. import __version__
    return f"paddle_tpu inference {__version__}"


def create_serving_engine(model, **kwargs):
    """Continuous-batching serving entry point — the multi-request
    analogue of create_predictor for autoregressive decode. Takes a
    live GPTForCausalLM (weights snapshotted now) and the
    paddle_tpu.serving knobs (num_slots, max_len, buckets, bucket_min,
    block_size, async_depth, donate_buffers, eos_id); returns
    a paddle_tpu.serving.ServingEngine whose add_request/step/run loop
    serves concurrent generations from a paged, donated KV cache
    with prefix-aware bucketed prefill, one-step-deep async decode
    pipelining and zero steady-state recompiles."""
    from ..serving import ServingEngine
    return ServingEngine(model, **kwargs)


class PredictorPool:
    """Reference: paddle_infer.PredictorPool — N predictors sharing one
    config (thread-per-predictor serving). Programs are jit-compiled
    and shared via the XLA executable cache, so clones are cheap."""

    def __init__(self, config, size=1):
        self._predictors = [Predictor(config) for _ in range(int(size))]

    def retrive(self, idx):  # reference spells it 'retrive'
        return self._predictors[idx]

    retrieve = retrive
