"""Registry-driven numeric-gradient sweep (VERDICT r3 item 6).

Every registered op is accounted for BY NAME: swept through a central
finite-difference check against jax autodiff at one canonical shape, or
waived with a reason. The sweep runs at the op layer (eager forward, no
per-evaluation rebind) in float64 so finite differences are sharp; the
executor-path gradient plumbing has its own tests. Modeled on the
reference's per-op check_numeric_gradient coverage in
tests/python/unittest/test_operator.py.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops.registry import (list_ops, get_op, find_op,
                                    normalize_attrs)

RNG = np.random.RandomState(42)
EPS = 1e-4
RTOL, ATOL = 5e-3, 1e-4


def _pos(*shape):
    return RNG.uniform(0.4, 0.9, shape)


def _sym(*shape):
    return RNG.uniform(-0.9, 0.9, shape)


# ---------------------------------------------------------------------------
# Explicit cases: op -> (attrs, inputs dict, grad input names)
# Inputs are numpy float64 unless an int dtype is baked in.
# ---------------------------------------------------------------------------
CASES = {
    "BatchNorm": ({"fix_gamma": False, "__train__": True},
                  {"data": _sym(2, 3, 4, 4), "gamma": _pos(3),
                   "beta": _sym(3), "moving_mean": np.zeros(3),
                   "moving_var": np.ones(3)},
                  ("data", "gamma", "beta"), (2e-2, 1e-3)),
    "BatchNorm_v1": ({"fix_gamma": False, "__train__": True},
                     {"data": _sym(2, 3, 4, 4), "gamma": _pos(3),
                      "beta": _sym(3), "moving_mean": np.zeros(3),
                      "moving_var": np.ones(3)},
                     ("data", "gamma", "beta"), (2e-2, 1e-3)),
    "_contrib_SyncBatchNorm": ({"fix_gamma": False, "__train__": True},
                               {"data": _sym(2, 3, 4, 4),
                                "gamma": _pos(3), "beta": _sym(3),
                                "moving_mean": np.zeros(3),
                                "moving_var": np.ones(3)},
                               ("data", "gamma", "beta"),
                               (2e-2, 1e-3)),
    "Convolution": ({"kernel": (3, 3), "num_filter": 4, "pad": (1, 1)},
                    {"data": _sym(1, 2, 5, 5),
                     "weight": _sym(4, 2, 3, 3), "bias": _sym(4)},
                    ("data", "weight", "bias")),
    "Convolution_v1": ({"kernel": (3, 3), "num_filter": 4},
                       {"data": _sym(1, 2, 5, 5),
                        "weight": _sym(4, 2, 3, 3), "bias": _sym(4)},
                       ("data", "weight", "bias")),
    "Deconvolution": ({"kernel": (2, 2), "num_filter": 3,
                       "no_bias": False},
                      {"data": _sym(1, 2, 4, 4),
                       "weight": _sym(2, 3, 2, 2), "bias": _sym(3)},
                      ("data", "weight", "bias")),
    "FullyConnected": ({"num_hidden": 4},
                       {"data": _sym(3, 5), "weight": _sym(4, 5),
                        "bias": _sym(4)},
                       ("data", "weight", "bias")),
    "LayerNorm": ({}, {"data": _sym(3, 6), "gamma": _pos(6),
                       "beta": _sym(6)}, ("data", "gamma", "beta")),
    "InstanceNorm": ({}, {"data": _sym(2, 3, 5), "gamma": _pos(3),
                          "beta": _sym(3)}, ("data", "gamma", "beta")),
    "LeakyReLU": ({"act_type": "prelu"},
                  {"data": _sym(3, 4), "gamma": _pos(4)},
                  ("data", "gamma")),
    "Embedding": ({"input_dim": 6, "output_dim": 4},
                  {"data": np.array([[0., 2.], [5., 1.]]),
                   "weight": _sym(6, 4)},
                  ("weight",)),
    "_contrib_SparseEmbedding": ({"input_dim": 6, "output_dim": 4},
                                 {"data": np.array([[0., 2.], [5., 1.]]),
                                  "weight": _sym(6, 4)},
                                 ("weight",)),
    "SequenceMask": ({"use_sequence_length": True, "value": 0.0},
                     {"data": _sym(4, 2, 3),
                      "sequence_length": np.array([2., 3.])},
                     ("data",)),
    "SequenceLast": ({"use_sequence_length": True},
                     {"data": _sym(4, 2, 3),
                      "sequence_length": np.array([2., 3.])},
                     ("data",)),
    "SequenceReverse": ({"use_sequence_length": True},
                        {"data": _sym(4, 2, 3),
                         "sequence_length": np.array([2., 3.])},
                        ("data",)),
    "Concat": ({"num_args": 2, "dim": 1},
               {"arg0": _sym(2, 3), "arg1": _sym(2, 4)},
               ("arg0", "arg1")),
    "concat": ({"num_args": 2, "dim": 1},
               {"arg0": _sym(2, 3), "arg1": _sym(2, 4)},
               ("arg0", "arg1")),
    "ElementWiseSum": ({"num_args": 3},
                       {"arg0": _sym(2, 3), "arg1": _sym(2, 3),
                        "arg2": _sym(2, 3)},
                       ("arg0", "arg1", "arg2")),
    "add_n": ({"num_args": 3},
              {"arg0": _sym(2, 3), "arg1": _sym(2, 3),
               "arg2": _sym(2, 3)},
              ("arg0", "arg1", "arg2")),
    "stack": ({"num_args": 2, "axis": 1},
              {"arg0": _sym(2, 3), "arg1": _sym(2, 3)},
              ("arg0", "arg1")),
    "_rnn_param_concat": ({"num_args": 2, "dim": 0},
                          {"arg0": _sym(4), "arg1": _sym(6)},
                          ("arg0", "arg1")),
    "khatri_rao": ({"num_args": 2},
                   {"arg0": _sym(3, 2), "arg1": _sym(4, 2)},
                   ("arg0", "arg1")),
    "take": ({}, {"a": _sym(5, 3),
                  "indices": np.array([[0., 2.], [4., 1.]])},
             ("a",)),
    "batch_take": ({}, {"a": _sym(3, 4),
                        "indices": np.array([1., 0., 3.])},
                   ("a",)),
    "choose_element_0index": ({}, {"lhs": _sym(3, 4),
                                   "rhs": np.array([1., 0., 3.])},
                              ("lhs",)),
    "pick": ({}, {"data": _sym(3, 4),
                  "index": np.array([1., 0., 3.])},
             ("data",)),
    "gather_nd": ({}, {"data": _sym(4, 3),
                       "indices": np.array([[1., 3.], [0., 2.]])},
                  ("data",)),
    "scatter_nd": ({"shape": (4, 3)},
                   {"data": _sym(2, 3),
                    "indices": np.array([[1., 3.]])},
                   ("data",)),
    "one_hot": ({"depth": 5}, {"indices": np.array([1., 3., 0.])}, ()),
    "softmax_cross_entropy": ({}, {"data": _sym(3, 5),
                                   "label": np.array([1., 0., 4.])},
                              ("data",)),
    "UpSampling": ({"scale": 2, "sample_type": "nearest",
                    "num_args": 1},
                   {"arg0": _sym(1, 2, 3, 3)}, ("arg0",)),
    "BilinearSampler": ({},
                        {"data": _sym(1, 2, 4, 4),
                         "grid": np.clip(_sym(1, 2, 3, 3), -0.8, 0.8)},
                        ("data", "grid")),
    "GridGenerator": None,   # unary via auto probe
    "ROIPooling": ({"pooled_size": (2, 2), "spatial_scale": 1.0},
                   {"data": _sym(1, 2, 6, 6),
                    "rois": np.array([[0., 0., 0., 3., 3.]])},
                   ("data",)),
    "ROIAlign": ({"pooled_size": (2, 2), "spatial_scale": 1.0},
                 {"data": _sym(1, 2, 6, 6),
                  "rois": np.array([[0., 0., 0., 3., 3.]])},
                 ("data",)),
    "_contrib_ROIAlign": ({"pooled_size": (2, 2), "spatial_scale": 1.0},
                          {"data": _sym(1, 2, 6, 6),
                           "rois": np.array([[0., 0., 0., 3., 3.]])},
                          ("data",)),
    # offsets drawn in (0.4, 0.9): sample points stay off the integer
    # grid where bilinear interpolation kinks make FD undefined
    "_contrib_DeformableConvolution": (
        {"kernel": (3, 3), "num_filter": 4, "pad": (1, 1)},
        {"data": _sym(1, 2, 5, 5), "offset": _pos(1, 18, 5, 5),
         "weight": _sym(4, 2, 3, 3), "bias": _sym(4)},
        ("data", "offset", "weight", "bias"), (2e-2, 1e-3)),
    "_contrib_PSROIPooling": (
        {"spatial_scale": 1.0, "output_dim": 2, "pooled_size": 2,
         "group_size": 2},
        {"data": _sym(1, 8, 6, 6),
         "rois": np.array([[0., 0., 0., 4., 4.], [0., 1., 1., 5., 5.]])},
        ("data",)),
    "_contrib_DeformablePSROIPooling": (
        {"spatial_scale": 1.0, "output_dim": 2, "group_size": 2,
         "pooled_size": 2, "sample_per_part": 2, "trans_std": 0.1},
        {"data": _sym(1, 8, 6, 6),
         "rois": np.array([[0., 0., 0., 4., 4.]]),
         "trans": _pos(1, 4, 2, 2) * 0.5},
        ("data", "trans"), (2e-2, 1e-3)),
    "_contrib_count_sketch": (
        {"out_dim": 4},
        {"data": _sym(3, 6),
         "h": np.array([0., 3., 1., 2., 3., 0.]),
         "s": np.array([1., -1., 1., 1., -1., 1.])},
        ("data", "s")),
    "SpatialTransformer": ({"transform_type": "affine",
                            "sampler_type": "bilinear",
                            "target_shape": (4, 4)},
                           {"data": _sym(1, 2, 4, 4),
                            "loc": np.array([[0.9, 0.05, 0.02,
                                              0.03, 0.9, 0.01]])},
                           ("data", "loc")),
    "Crop": ({"num_args": 1, "h_w": (2, 2), "offset": (1, 1)},
             {"arg0": _sym(1, 2, 5, 5)}, ("arg0",)),
    "_getitem": ({"key": (1,)}, {"data": _sym(3, 4)}, ("data",)),
    "_slice_assign_scalar": ({"key": (1,), "value": 0.5},
                             {"data": _sym(3, 4)}, ("data",)),
    "_contrib_index_copy": ({},
                            {"data": _sym(5, 3),
                             "index": np.array([1., 3.]),
                             "new_tensor": _sym(2, 3)},
                            ("data", "new_tensor")),
    "_contrib_boolean_mask": ({},
                              {"data": _sym(4, 3),
                               "index": np.array([1., 0., 1., 1.])},
                              ("data",)),
    "_contrib_edge_id": None,
    "linalg_gemm": ({}, {"A": _sym(3, 4), "B": _sym(4, 2),
                         "C": _sym(3, 2)}, ("A", "B", "C")),
    "linalg_gemm2": ({}, {"A": _sym(3, 4), "B": _sym(4, 2)},
                     ("A", "B")),
    "linalg_syrk": ({}, {"A": _sym(3, 4)}, ("A",)),
    "linalg_trmm": ({}, {"A": np.tril(_pos(3, 3) + np.eye(3)),
                         "B": _sym(3, 4)}, ("A", "B")),
    "linalg_trsm": ({}, {"A": np.tril(_pos(3, 3) + 2 * np.eye(3)),
                         "B": _sym(3, 4)}, ("A", "B")),
    "linalg_potrf": ({}, {"A": None}, ("A",)),  # filled below (SPD)
    "linalg_potri": ({}, {"A": None}, ("A",)),
    "linalg_det": ({}, {"A": None}, ("A",)),
    "linalg_slogdet": ({}, {"A": None}, ("A",)),
    "linalg_inverse": ({}, {"A": None}, ("A",)),
    "linalg_sumlogdiag": ({}, {"A": None}, ("A",)),
    "linalg_extractdiag": ({}, {"A": _sym(3, 3)}, ("A",)),
    "linalg_extracttrian": ({}, {"A": _sym(3, 3)}, ("A",)),
    "linalg_makediag": ({}, {"A": _sym(3)}, ("A",)),
    "CTCLoss": None,
    "ctc_loss": None,
    "_contrib_ctc_loss": None,
    "dot": ({}, {"lhs": _sym(3, 4), "rhs": _sym(4, 2)},
            ("lhs", "rhs")),
    "batch_dot": ({}, {"lhs": _sym(2, 3, 4), "rhs": _sym(2, 4, 2)},
                  ("lhs", "rhs")),
    "arcsin": ({}, {"data": _sym(2, 3) * 0.7}, ("data",)),
    "arccos": ({}, {"data": _sym(2, 3) * 0.7}, ("data",)),
    "arctanh": ({}, {"data": _sym(2, 3) * 0.7}, ("data",)),
    "erfinv": ({}, {"data": _sym(2, 3) * 0.6}, ("data",)),
    "arccosh": ({}, {"data": 1.2 + _pos(2, 3)}, ("data",)),
    "_div_scalar": ({"scalar": 1.7}, {"data": _sym(2, 3)}, ("data",)),
    "_mod_scalar": ({"scalar": 1.7}, {"data": _pos(2, 3)}, ("data",)),
    "Correlation": ({"kernel_size": 1, "max_displacement": 1,
                     "pad_size": 1},
                    {"data1": _sym(1, 2, 5, 5), "data2": _sym(1, 2, 5, 5)},
                    ("data1", "data2")),
    "MultiBoxPrior": ({"sizes": (0.5,), "ratios": (1.0, 2.0)},
                      {"data": _sym(1, 2, 5, 5)}, ()),
    "_contrib_MultiBoxPrior": ({"sizes": (0.5,), "ratios": (1.0, 2.0)},
                               {"data": _sym(1, 2, 5, 5)}, ()),
    "Pad": ({"mode": "constant",
             "pad_width": (0, 0, 0, 0, 1, 1, 1, 1)},
            {"data": _sym(1, 2, 3, 3)}, ("data",)),
    "pad": ({"mode": "constant",
             "pad_width": (0, 0, 0, 0, 1, 1, 1, 1)},
            {"data": _sym(1, 2, 3, 3)}, ("data",)),
    "_image_resize": ({"size": 4}, {"data": _pos(5, 5, 3)}, ("data",)),
    "_image_to_tensor": ({}, {"data": _pos(4, 4, 3)}, ("data",)),
    "_image_totensor": ({}, {"data": _pos(4, 4, 3)}, ("data",)),
    "_contrib_AdaptiveAvgPooling2D": ({"output_size": 2},
                                      {"data": _sym(1, 2, 4, 4)},
                                      ("data",)),
    "_contrib_BilinearResize2D": ({"height": 4, "width": 4},
                                  {"data": _sym(1, 2, 3, 3)},
                                  ("data",)),
    "broadcast_to": ({"shape": (4, 3)}, {"data": _sym(1, 3)},
                     ("data",)),
    "depth_to_space": ({"block_size": 2}, {"data": _sym(1, 4, 2, 2)},
                       ("data",)),
    "space_to_depth": ({"block_size": 2}, {"data": _sym(1, 2, 4, 4)},
                       ("data",)),
    "_scatter_set_nd": ({"shape": (4, 3)},
                        {"lhs": _sym(4, 3),
                         "indices": np.array([[1., 3.]]),
                         "rhs": _sym(2, 3)},
                        ("lhs", "rhs")),
    "_sparse_retain": ({}, {"data": _sym(4, 3),
                            "indices": np.array([0., 2.])},
                       ("data",)),
    "_contrib_ifft": ({}, {"data": _sym(2, 8)}, ("data",),
                      (5e-2, 5e-3)),   # fp32-internal DFT
    "_contrib_fft": ({}, {"data": _sym(2, 8)}, ("data",),
                     (5e-2, 5e-3)),    # fp32-internal DFT
    # serving's cached-KV decode step: swept on the jnp composition
    # (the Pallas decode kernel is forward-only, pinned against this
    # same composition in test_decode / test_chip_compile)
    "_contrib_decode_attention": (
        {"impl": "dense"},
        {"query": _sym(2, 1, 2, 4), "key_cache": _sym(2, 6, 2, 4),
         "value_cache": _sym(2, 6, 2, 4),
         "lengths": np.array([4., 6.])},
        ("query", "key_cache", "value_cache")),
    "where": ({}, {"condition": np.array([[1., 0.], [0., 1.],
                                          [1., 1.]]),
                   "x": _sym(3, 2), "y": _sym(3, 2)},
              ("x", "y")),
}

_SPD = np.eye(3) * 2.0 + 0.3 * _sym(3, 3) @ _sym(3, 3).T
for _n in ("linalg_potrf", "linalg_potri", "linalg_det",
           "linalg_slogdet", "linalg_inverse", "linalg_sumlogdiag"):
    CASES[_n][1]["A"] = _SPD.copy()

# aliases share cases
for _a, _b in (("_linalg_gemm", "linalg_gemm"),
               ("_linalg_gemm2", "linalg_gemm2"),
               ("_linalg_syrk", "linalg_syrk"),
               ("_linalg_trmm", "linalg_trmm"),
               ("_linalg_trsm", "linalg_trsm"),
               ("_linalg_potrf", "linalg_potrf"),
               ("_linalg_potri", "linalg_potri"),
               ("_linalg_det", "linalg_det"),
               ("_linalg_slogdet", "linalg_slogdet"),
               ("_linalg_inverse", "linalg_inverse"),
               ("_linalg_sumlogdiag", "linalg_sumlogdiag"),
               ("_linalg_extractdiag", "linalg_extractdiag"),
               ("_linalg_extracttrian", "linalg_extracttrian"),
               ("_linalg_makediag", "linalg_makediag")):
    CASES[_a] = CASES[_b]

# ---------------------------------------------------------------------------
# Waivers: op -> reason. Every name here is deliberate.
# ---------------------------------------------------------------------------
# loss-head ops: backward emits the implicit loss gradient regardless
# of the head cotangent, so FD cannot apply — but they are NOT waived:
# each is pinned EXACTLY against the reference kernel's formula in
# tests/test_head_op_gradients.py (ANALYTIC_COVERED there must match)
ANALYTIC = {
    "SoftmaxOutput": "exact (softmax - onehot) pin incl. ignore/"
                     "multi_output/smooth (test_head_op_gradients)",
    "Softmax": "alias of SoftmaxOutput (test_head_op_gradients)",
    "SVMOutput": "exact L1/L2 hinge pin (test_head_op_gradients)",
    "LinearRegressionOutput": "exact minus pin (test_head_op_gradients)",
    "LogisticRegressionOutput":
        "exact sigmoid/minus pin (test_head_op_gradients)",
    "MAERegressionOutput":
        "exact minus_sign pin (test_head_op_gradients)",
}

# parameter-mutating optimizer kernels: each pinned EXACTLY against a
# numpy transcription of the reference kernel, incl. wd and both
# clip_gradient settings, in tests/test_optimizer_kernels.py
for _n in ("sgd_update", "sgd_mom_update", "mp_sgd_update",
           "mp_sgd_mom_update", "nag_mom_update", "adam_update",
           "rmsprop_update", "rmspropalex_update", "ftrl_update",
           "ftml_update", "signsgd_update", "signum_update",
           "adagrad_update", "_sparse_adagrad_update",
           "_contrib_group_adagrad_update", "_contrib_adamw_update",
           "_contrib_mp_adamw_update", "multi_sgd_update",
           "multi_sgd_mom_update", "multi_mp_sgd_update",
           "multi_mp_sgd_mom_update"):
    ANALYTIC[_n] = "exact reference-kernel pin (test_optimizer_kernels)"

WAIVED = {
    # stochastic samplers: no meaningful numeric gradient;
    # distribution moments + seeding determinism pinned in
    # tests/test_random_samplers.py
    "_random_uniform": "sampler (moments pinned in test_random_samplers)", "_random_normal": "sampler (moments pinned in test_random_samplers)",
    "_random_gamma": "sampler (moments pinned in test_random_samplers)", "_random_exponential": "sampler (moments pinned in test_random_samplers)",
    "_random_poisson": "sampler (moments pinned in test_random_samplers)", "_random_negative_binomial": "sampler (moments pinned in test_random_samplers)",
    "_random_generalized_negative_binomial": "sampler (moments pinned in test_random_samplers)",
    "_random_randint": "sampler (moments pinned in test_random_samplers)",
    "_sample_uniform": "sampler (moments pinned in test_random_samplers)", "_sample_normal": "sampler (moments pinned in test_random_samplers)",
    "_sample_gamma": "sampler (moments pinned in test_random_samplers)", "_sample_exponential": "sampler (moments pinned in test_random_samplers)",
    "_sample_poisson": "sampler (moments pinned in test_random_samplers)", "_sample_negative_binomial": "sampler (moments pinned in test_random_samplers)",
    "_sample_generalized_negative_binomial": "sampler (moments pinned in test_random_samplers)",
    # constant creators: no tensor inputs
    "_zeros": "no inputs", "_ones": "no inputs", "_full": "no inputs",
    "_eye": "no inputs", "_arange": "no inputs",
    "_linspace": "no inputs", "_zeros_without_dtype": "no inputs",
    # integer/assignment/graph machinery
    "_histogram": "integer counting output",
    "Custom": "user-defined body (test_custom_op)",
    "_foreach": "control flow (test_control_flow)",
    "_while_loop": "control flow (test_control_flow)",
    "_cond": "control flow (test_control_flow)",
    "RNN": "fused RNN: pinned vs unfused cells in test_rnn",
    # quantized kernels: integer domains (test_op_breadth pins numerics)
    "_contrib_quantize": "int8 path (test_op_breadth)",
    "_contrib_dequantize": "int8 path (test_op_breadth)",
    "_contrib_requantize": "int8 path (test_op_breadth)",
    "_contrib_quantized_conv": "int8 path (test_op_breadth)",
    "_contrib_quantized_fully_connected": "int8 path (test_op_breadth)",
    "_contrib_quantized_pooling": "int8 path (test_op_breadth)",
    "_contrib_quantized_flatten": "int8 path (test_op_breadth)",
    "_contrib_quantized_concat": "int8 path (test_op_breadth)",
    # detection target/box assembly: piecewise-constant box logic
    "MultiBoxTarget": "box matching: piecewise constant",
    "MultiBoxDetection": "box decode+NMS: piecewise constant",
    "_contrib_Proposal":
        "RPN proposal: discrete top-k/NMS selection (test_deformable_ops)",
    "Proposal":
        "RPN proposal: discrete top-k/NMS selection (test_deformable_ops)",
    "_contrib_MultiProposal":
        "RPN proposal: discrete top-k/NMS selection (test_deformable_ops)",
    "MultiProposal":
        "RPN proposal: discrete top-k/NMS selection (test_deformable_ops)",
    "_contrib_MultiBoxTarget": "box matching: piecewise constant",
    "_contrib_MultiBoxDetection": "box decode+NMS: piecewise constant",
    # eigendecomposition: gradient defined only for distinct eigenvalues
    # and jax's syevd vjp is iterative; pinned forward in test_op_breadth
    "linalg_syevd": "eigh vjp needs distinct spectrum",
    "_linalg_syevd": "eigh vjp needs distinct spectrum",
    "linalg_gelqf": "LQ factor vjp unsupported in jax",
    "_linalg_gelqf": "LQ factor vjp unsupported in jax",
    # CTC: fp32-internal DP, gradient pinned separately
    "CTCLoss": "fp32 DP loss (test_operator pins grads)",
    "ctc_loss": "fp32 DP loss (test_operator pins grads)",
    "_contrib_ctc_loss": "fp32 DP loss (test_operator pins grads)",
    "_contrib_flash_attention": "kernel path pinned in "
                                "test_flash_attention (fwd+bwd)",
    "_contrib_edge_id": "graph query: integer adjacency lookup",
    "_contrib_dgl_csr_neighbor_uniform_sample":
        "host graph sampling: integer structure (test_dgl_ops)",
    "_contrib_dgl_csr_neighbor_non_uniform_sample":
        "host graph sampling: integer structure (test_dgl_ops)",
    "_contrib_dgl_subgraph":
        "host graph sampling: integer structure (test_dgl_ops)",
    "_contrib_dgl_adjacency":
        "host graph sampling: integer structure (test_dgl_ops)",
    "_contrib_dgl_graph_compact":
        "host graph sampling: integer structure (test_dgl_ops)",
    "GridGenerator": "affine grid: pinned in test_op_breadth",
    "BlockGrad": "gradient-blocking op: zero grad by definition",
    "stop_gradient": "gradient-blocking op: zero grad by definition",
    "MakeLoss": "loss head: gradient is grad_scale by definition",
    "make_loss": "loss head: gradient is grad_scale by definition",
    "_unravel_index": "integer index arithmetic",
    "_contrib_box_iou": "IoU: kinked at box-overlap boundaries",
}


def _auto_case(op):
    """Generic case for unary 'data' and binary elementwise ops."""
    names = op.arg_names
    if names == ["data"] and not op.key_var_num_args \
            and not op.arg_names_fn:
        return {}, {"data": _pos(2, 3) + 0.35}, ("data",)
    if names in (["lhs", "rhs"], ["data1", "data2"], ["a", "b"]):
        lhs = _pos(2, 3) + 0.35
        # keep |lhs - rhs| > 0.05: min/max/mod-style ops have
        # subgradient kinks at ties where FD is undefined
        delta = (RNG.uniform(0.05, 0.4, lhs.shape)
                 * np.where(RNG.rand(*lhs.shape) < 0.5, -1.0, 1.0))
        return {}, {names[0]: lhs, names[1]: lhs + delta}, tuple(names)
    return None


def _collect():
    plans = []
    unaccounted = []
    for name in list_ops():
        op = get_op(name)
        if name in WAIVED or name in ANALYTIC:
            continue
        case = CASES.get(name)
        if case is None and name in CASES:
            case = _auto_case(op)          # explicit "use auto probe"
        if case is None:
            case = _auto_case(op)
        if case is None:
            unaccounted.append(name)
            continue
        plans.append((name, case))
    return plans, unaccounted


_PLANS, _UNACCOUNTED = _collect()


def test_every_op_swept_or_waived():
    """Registry coverage: no op may be silently unclassified."""
    assert not _UNACCOUNTED, (
        "ops neither swept nor waived by name: %s" % _UNACCOUNTED)
    waived_unknown = [n for n in WAIVED if find_op(n) is None]
    assert not waived_unknown
    # the ANALYTIC category is honest only if every entry really has
    # its dedicated exact-gradient test
    from test_head_op_gradients import ANALYTIC_COVERED as _heads
    from test_optimizer_kernels import ANALYTIC_COVERED as _optims
    covered = set(_heads) | set(_optims)
    assert set(ANALYTIC) == covered, (set(ANALYTIC) ^ covered)
    analytic_unknown = [n for n in ANALYTIC if find_op(n) is None]
    assert not analytic_unknown


@pytest.mark.parametrize("name,case", _PLANS,
                         ids=[n for n, _ in _PLANS])
def test_numeric_gradient(name, case):
    op = get_op(name)
    attrs, inputs, grad_names = case[:3]
    rtol, atol = case[3] if len(case) > 3 else (RTOL, ATOL)
    nattrs = normalize_attrs(op, dict(attrs))
    arg_order = op.resolve_arg_names(nattrs, num_inputs=len(inputs))
    # cases may name variadic inputs arg0..argN directly
    if set(arg_order) != set(inputs):
        arg_order = list(inputs)
    n_out = op.resolve_num_outputs(nattrs)

    with jax.enable_x64(True):
        vals = [jnp.asarray(np.asarray(inputs[n], np.float64))
                for n in arg_order]
        rng_key = jax.random.PRNGKey(0) if op.needs_rng else None
        projs = {}

        def f(*arrs):
            kw = {"rng": rng_key} if op.needs_rng else {}
            out = op.forward(nattrs, *arrs, **kw)
            if not isinstance(out, (tuple, list)):
                out = (out,)
            total = jnp.float64(0)
            for i, o in enumerate(out[:n_out]):
                if not jnp.issubdtype(o.dtype, jnp.floating):
                    continue
                if i not in projs:
                    projs[i] = jnp.asarray(
                        np.random.RandomState(7 + i)
                        .uniform(-1, 1, o.shape))
                total = total + jnp.sum(o.astype(jnp.float64) * projs[i])
            return total

        gpos = [arg_order.index(n) for n in grad_names]
        if not gpos:
            float(f(*vals))                # forward-only smoke
            return
        analytic = jax.grad(f, argnums=tuple(gpos))(*vals)
        # the FD loop evaluates f twice per element: past a few dozen
        # elements one compile of f is far cheaper than that many
        # eager op-by-op evaluations (the deformable ops took minutes)
        n_fd = sum(int(np.asarray(vals[p]).size) for p in gpos)
        f_fd = jax.jit(f) if n_fd > 64 else f

        for gi, p in enumerate(gpos):
            base = np.asarray(vals[p], np.float64)
            an = np.asarray(analytic[gi], np.float64)
            num = np.zeros_like(base).ravel()
            flat = base.ravel()
            for j in range(flat.size):
                vp, vm = flat.copy(), flat.copy()
                vp[j] += EPS
                vm[j] -= EPS
                a_p = list(vals)
                a_p[p] = jnp.asarray(vp.reshape(base.shape))
                a_m = list(vals)
                a_m[p] = jnp.asarray(vm.reshape(base.shape))
                num[j] = (float(f_fd(*a_p)) - float(f_fd(*a_m))) \
                    / (2 * EPS)
            np.testing.assert_allclose(
                an.ravel(), num, rtol=rtol, atol=atol,
                err_msg="%s: d/d%s mismatch" % (name, arg_order[p]))
