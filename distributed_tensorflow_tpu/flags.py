"""Flag system with the reference's ``tf.app.flags`` surface.

The reference's public interface is 10 flags + ``tf.app.run()``
(``MNISTDist.py:13-31,197-198``). This module reproduces that API —
``DEFINE_string/integer/float/boolean``, a lazily-parsed ``FLAGS``
singleton, and ``run(main)`` — over argparse, with zero TF dependency.

CLI compatibility is a hard requirement (BASELINE.json): the same launch
scripts that address GPU workers must address TPU VMs, so ``--job_name``,
``--task_index``, ``--ps_hosts``, ``--worker_hosts`` keep their exact
meanings.
"""

from __future__ import annotations

import argparse
import sys
from typing import Any, Callable


class _FlagValues:
    """Lazy-parsing flag namespace (attribute access parses argv once),
    mirroring the TF-0.x FLAGS behavior the reference relies on."""

    def __init__(self):
        self.__dict__["_defs"] = {}  # name -> (type_fn, default, help)
        self.__dict__["_values"] = None
        self.__dict__["_extra_argv"] = []
        self.__dict__["_validators"] = []  # fns(values) run after parse

    def _define(self, name: str, default, help_str: str, type_fn: Callable):
        if self._values is not None:
            # late definition after parse: make it visible with its default
            self._values[name] = default
        self._defs[name] = (type_fn, default, help_str)

    def _register_validator(self, fn: Callable):
        """Cross-flag consistency check run at PARSE time: ``fn(values)``
        raises ValueError with an actionable message. This is how config
        mistakes (e.g. a --virtual_stages/--num_blocks mismatch) surface
        at the command line instead of minutes later mid-trace.
        Idempotent by function identity."""
        if fn not in self._validators:
            self._validators.append(fn)

    def _parse(self, argv=None):
        parser = argparse.ArgumentParser(allow_abbrev=False)
        for name, (type_fn, default, help_str) in self._defs.items():
            if type_fn is bool:
                parser.add_argument(
                    f"--{name}",
                    type=_parse_bool,
                    default=default,
                    nargs="?",
                    const=True,
                    help=help_str,
                )
            else:
                parser.add_argument(f"--{name}", type=type_fn, default=default, help=help_str)
        ns, extra = parser.parse_known_args(
            sys.argv[1:] if argv is None else list(argv)
        )
        self.__dict__["_values"] = vars(ns)
        self.__dict__["_extra_argv"] = extra
        for check in self._validators:
            check(self._values)
        return extra

    def __getattr__(self, name: str) -> Any:
        if name.startswith("_"):
            raise AttributeError(name)
        if self._values is None:
            self._parse()
        try:
            return self._values[name]
        except KeyError:
            raise AttributeError(f"unknown flag {name!r}") from None

    def __setattr__(self, name: str, value: Any):
        if self._values is None:
            self._parse()
        self._values[name] = value

    def _reset(self):
        """Testing hook: forget parsed values (definitions stay)."""
        self.__dict__["_values"] = None
        self.__dict__["_extra_argv"] = []


def _parse_bool(s):
    if isinstance(s, bool):
        return s
    if str(s).lower() in ("1", "true", "t", "yes", "y"):
        return True
    if str(s).lower() in ("0", "false", "f", "no", "n"):
        return False
    raise argparse.ArgumentTypeError(f"invalid boolean {s!r}")


FLAGS = _FlagValues()


def DEFINE_string(name: str, default: str | None, help_str: str = ""):
    FLAGS._define(name, default, help_str, str)


def DEFINE_integer(name: str, default: int | None, help_str: str = ""):
    FLAGS._define(name, default, help_str, int)


def DEFINE_float(name: str, default: float | None, help_str: str = ""):
    FLAGS._define(name, default, help_str, float)


def DEFINE_boolean(name: str, default: bool | None, help_str: str = ""):
    FLAGS._define(name, default, help_str, bool)


DEFINE_bool = DEFINE_boolean


def run(main: Callable | None = None, argv=None):
    """``tf.app.run`` parity (MNISTDist.py:198): parse flags, call
    ``main(unparsed_argv)``, exit with its return code. A parse-time
    validator rejection exits 2 with the message on stderr — the
    argparse usage-error convention, so a bad flag combination looks
    the same to launch scripts however it was caught."""
    try:
        extra = FLAGS._parse(argv)
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        sys.exit(2)
    from distributed_tensorflow_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()  # before main: nothing has compiled yet
    main = main or sys.modules["__main__"].main
    sys.exit(main([sys.argv[0]] + extra))


COORD_STEPS_DEFAULT = 50


def coord_steps_from_flags(FLAGS) -> int:
    """The one flag→feature mapping for ``--coord_steps`` (multi-host
    vote cadence), shared by every loop that builds a _HostCoordinator so
    the flag default and the flag-less library default cannot diverge."""
    return int(getattr(FLAGS, "coord_steps", COORD_STEPS_DEFAULT))


def define_reference_flags():
    """The reference's exact 10-flag surface (MNISTDist.py:13-31) plus this
    build's extensions. Idempotent."""
    if "job_name" in FLAGS._defs:
        return
    # --- reference flags, same names/defaults/meanings ---
    DEFINE_string("data_dir", "/tmp/mnist-data", "Directory for string mnist data")
    DEFINE_string("ps_hosts", "", "Comma-separated list of hostname:port pairs")
    DEFINE_string("worker_hosts", "", "Comma-separated list of hostname:port pairs")
    DEFINE_string("job_name", "", "One of 'ps', 'worker'")
    DEFINE_integer("task_index", 0, "Index of task within the job")
    DEFINE_integer("hidden_units", 100, "Number of units in the hidden layer of the NN")
    DEFINE_integer("batch_size", 128, "Training batchsize")
    DEFINE_integer("training_iter", 10000, "Training iteration")
    DEFINE_float("learning_rate", 0.001, "Learning rate")
    DEFINE_integer("display_step", 100, "display step")
    # --- build extensions (TPU-native modes and configs) ---
    DEFINE_string("mode", "auto", "Parallel mode: auto|local|sync|ps. auto = "
                  "'ps' roles when --ps_hosts is set (reference semantics), "
                  "else sync DP over all local devices")
    DEFINE_string("model", "deep_cnn", "Model architecture: "
                  "deep_cnn|mlp|resnet20|resnet32|transformer|lm (mlp "
                  "reads --hidden_units; lm is the causal next-token "
                  "family and requires --dataset lm)")
    DEFINE_string("dataset", "mnist", "Dataset: mnist|fashion_mnist|"
                  "cifar10|lm (lm: procedural associative-recall token "
                  "sequences for the causal-LM family; --seq_len/"
                  "--vocab_size shape it)")
    DEFINE_string("optimizer", "sgd", "Optimizer: sgd|momentum|adam (reference: sgd)")
    DEFINE_float("weight_decay", 0.0, "Decoupled weight decay: the update "
                 "subtracts lr*wd*param alongside the gradient step "
                 "(AdamW semantics for adam; classic L2 for plain sgd). "
                 "local/sync/TP/device_data modes; ps mode rejects it")
    DEFINE_float("keep_prob", 0.75, "Dropout keep probability during training. "
                 "The reference defines DROPOUT=0.75 but feeds 1.0 (disabled); "
                 "this build applies it")
    DEFINE_string("logdir", "/tmp/train_logs", "Checkpoint/metrics directory (reference default)")
    DEFINE_integer("save_model_secs", 600,
                   "Checkpoint cadence in seconds (reference default). In "
                   "multi-host runs saves are quantized to --coord_steps "
                   "boundaries (the cadenced stop/save vote), so a due "
                   "save can land up to coord_steps steps late")
    DEFINE_integer("max_to_keep", 5, "Checkpoints retained before GC "
                   "(TF Saver's default); older ones are deleted")
    DEFINE_integer("seed", 0, "PRNG seed")
    DEFINE_boolean("bf16", False, "Run matmuls/convs in bfloat16 on the MXU")
    DEFINE_boolean("pallas", False, "Use the fused Pallas kernel for the "
                   "dominant FC layer (deep_cnn only)")
    DEFINE_boolean("test_eval", True, "Evaluate on the test split at the end "
                   "(the reference never does; targets require it)")
    DEFINE_boolean("eval_only", False, "Restore the latest checkpoint from "
                   "--logdir and evaluate the full test split — no "
                   "training. Works on checkpoints from every mode")
    DEFINE_integer("eval_step", 0, "If > 0, also evaluate on the FULL test "
                   "split every this many steps (logged as test_accuracy/"
                   "test_loss scalars). 0 = end-of-run only; the reference "
                   "never touches the test split at all")
    DEFINE_boolean("shard_data", False, "Give each worker a disjoint data shard "
                   "(reference: every worker samples the full dataset)")
    DEFINE_string("profile_dir", "", "If set, capture a jax.profiler trace of "
                  "--profile_steps post-compile training steps into this dir")
    DEFINE_integer("profile_steps", 10, "Number of steps in the profiler window")
    DEFINE_integer("validation_size", 0, "Examples held out of the train split "
                   "as a validation DataSet (0 = none, reference behavior). "
                   "With --eval_step the periodic evals run on this split "
                   "(validation_accuracy/validation_loss scalars) and the "
                   "test split is touched only by the final --test_eval")
    DEFINE_boolean("raw_input", False, "Feed uint8 images + int32 labels and "
                   "normalize on device (4x less host->device traffic; "
                   "fastest path on bandwidth-limited links)")
    DEFINE_boolean("device_data", False, "Stage the train split into HBM once "
                   "and sample batches ON DEVICE inside the compiled step "
                   "(zero host->device bytes per step; lax.scan runs "
                   "--device_chunk steps per dispatch). Composes with every "
                   "parallel mode: plain DP/TP, --seq_parallel (token-"
                   "sharded split), --pipeline and --expert_parallel (data-"
                   "sharded split, per-shard salted PRNG streams). Training "
                   "batches are sampled with replacement rather than the "
                   "reference's shuffled-epoch walk; display-step evals keep "
                   "reference semantics where a host batch exists (PP "
                   "displays the step's own training metrics instead)")
    DEFINE_integer("device_chunk", 50, "Steps per compiled scan chunk in "
                   "--device_data mode (clamped to divide display_step)")
    DEFINE_float("clip_norm", 0.0, "If > 0, clip gradients to this global "
                 "L2 norm before the optimizer update (every mode except "
                 "ps, which keeps reference parity). Under --pipeline / "
                 "--expert_parallel the squared norm is psum'd over the "
                 "model axis before scaling (stage/expert shards are exact "
                 "partials), so the clipped trajectory exactly matches the "
                 "single-device one and replicated leaves stay bit-"
                 "identical. Guards against early loss spikes at high "
                 "learning rates")
    DEFINE_integer("model_axis", 1, "Tensor-parallel ways on the mesh's "
                   "'model' axis (sync mode): the CNN's FC stack is "
                   "column/row-split and XLA inserts the collectives. "
                   "1 = pure data parallelism (reference-equivalent). "
                   "With --seq_parallel this is the SEQUENCE ways instead")
    DEFINE_boolean("seq_parallel", False, "Sequence/context parallelism "
                   "(sync mode, --model transformer only): the token axis "
                   "shards --model_axis ways over the mesh's 'model' axis, "
                   "attention runs as a RING (k/v blocks rotating over "
                   "ICI with online-softmax accumulation), per-device "
                   "activation memory stays one token block regardless "
                   "of context length")
    DEFINE_boolean("sp_span_hosts", False, "--seq_parallel only: allow "
                   "the token axis to SPAN processes — ring hops between "
                   "hosts ride DCN, and the context length is no longer "
                   "bounded by one host's chips. Every process then draws "
                   "the SAME global batch (shared seed; hosts in a data "
                   "row hold token-slices of the same sequences) and "
                   "uploads only its tile. Default: the token axis must "
                   "stay within each host's chips")
    DEFINE_string("lr_schedule", "constant", "Learning-rate schedule: "
                  "constant|cosine|linear|exponential — evaluated inside "
                  "the compiled step (reference: constant). Decays over "
                  "--decay_steps from --learning_rate")
    DEFINE_integer("warmup_steps", 0, "Linear learning-rate warmup steps "
                   "before --lr_schedule takes over (0 = none)")
    DEFINE_integer("decay_steps", 0, "Schedule decay horizon in steps "
                   "(0 = the full --training_iter budget)")
    DEFINE_float("decay_rate", 0.96, "Decay factor per --decay_steps for "
                 "--lr_schedule=exponential")
    DEFINE_boolean("augment", False, "On-device data augmentation compiled "
                   "into the train step: zero-pad by --augment_pad, random "
                   "crop back, and — for 3-channel natural images only — "
                   "random horizontal flip (digits are never mirrored). "
                   "Zero host cost. local/sync/TP and --device_data modes")
    DEFINE_integer("augment_pad", 4, "Padding for --augment's random crop")
    DEFINE_integer("accum_steps", 1, "Gradient accumulation: split each "
                   "batch into this many equal microbatches, one backward "
                   "pass each (lax.scan — live activations are one "
                   "microbatch's worth), average, then a single optimizer "
                   "update. local/sync/TP modes; incompatible with "
                   "--device_data (whose batches are already sampled "
                   "on device per step)")
    DEFINE_integer("seq_len", 256, "Context length for --dataset lm "
                   "(tokens per training sequence; targets are the "
                   "sequence shifted one token)")
    DEFINE_integer("vocab_size", 64, "Vocabulary for --dataset lm")
    DEFINE_integer("d_model", 128, "Transformer width (transformer|lm)")
    DEFINE_integer("num_heads", 4, "Attention heads (transformer|lm)")
    DEFINE_integer("num_blocks", 2, "Transformer blocks (transformer|lm)")
    DEFINE_integer("attn_block", 0, "If > 0, single-device attention "
                   "streams over key/value blocks of this many tokens "
                   "(online softmax — O(S*block) peak memory instead of "
                   "the dense O(S^2) score matrix; the one-chip "
                   "long-context path). lm model only; mutually "
                   "exclusive with --seq_parallel's ring attention")
    DEFINE_integer("ce_block", 0, "If > 0, the LM loss head streams "
                   "over row blocks of this many tokens (custom-VJP "
                   "softmax-CE — the (B,S,V) f32 logits never "
                   "materialize; O(block*V) peak both passes). The "
                   "large-vocab half of the long-context memory story; "
                   "lm model only")
    DEFINE_boolean("pipeline", False, "GPipe-style pipeline parallelism "
                   "for --model lm: transformer blocks staged "
                   "--model_axis ways over the mesh's 'model' axis, "
                   "activations ppermute stage-to-stage while every "
                   "stage works a different microbatch "
                   "(parallel/pipeline_parallel.py). Mutually exclusive "
                   "with --seq_parallel; num_blocks must divide by "
                   "--model_axis. Composes with --device_data (the "
                   "resident chunked sampler), --clip_norm (axis-"
                   "aware) and --virtual_stages (the interleaved "
                   "schedule — a ~V-fold smaller pipeline bubble)")
    DEFINE_integer("pp_microbatches", 0, "Microbatches per step under "
                   "--pipeline (0 = the stage count, the GPipe "
                   "default); must divide the per-data-shard batch, "
                   "and by --model_axis when --virtual_stages > 1 "
                   "(the interleaved schedule works microbatches in "
                   "rounds of the stage count)")
    DEFINE_integer("virtual_stages", 1, "Interleaved virtual-stage "
                   "pipeline schedule (Megatron-LM) for --pipeline: "
                   "each stage owns this many NONCONTIGUOUS round-"
                   "robin block groups, activations make V shorter "
                   "trips around the ppermute ring, and the fill/"
                   "drain bubble shrinks ~V-fold (useful-tick "
                   "fraction M*V/(M*V+K-1) vs GPipe's M/(M+K-1)). "
                   "Bit-identical trajectories to the default V=1; "
                   "checkpoints stay layout-independent. Requires "
                   "num_blocks divisible by model_axis*virtual_stages "
                   "and microbatches divisible by model_axis")
    DEFINE_string("pp_schedule", "auto", "Pipeline tick schedule for "
                  "--pipeline: auto (default: interleaved when "
                  "--virtual_stages > 1, else gpipe — the pre-flag "
                  "behavior), gpipe, interleaved, or zb (zero-bubble, "
                  "ZB-H1 family: backward splits into activation-grad "
                  "B and weight-grad W ticks and the deferred W ticks "
                  "fill the cooldown bubble — useful-tick fraction "
                  "strictly above interleaved at the same layout). "
                  "All three compute the same function: trajectories "
                  "are bit-identical across schedules at the same "
                  "(K, M, V) and checkpoints restore across them "
                  "bitwise. zb composes with --virtual_stages and "
                  "needs >= 2 blocks per virtual-stage group")
    DEFINE_integer("moe_experts", 0, "If > 0, the LM's MLPs become "
                   "top-1 Switch mixture-of-experts layers with this "
                   "many experts (ops/moe.py); the training loss adds "
                   "--moe_aux times the load-balance term")
    DEFINE_float("moe_capacity", 1.25, "Per-expert token capacity "
                 "factor (tokens beyond ceil(cf*T/E) drop to the "
                 "residual stream — Switch semantics). Under --moe_top_k: "
                 "the sorted buffer's rows over the pairs uniform routing "
                 "expects; they are memory, the experts' products and "
                 "the dispatch (gather, add-back) cost the pairs that "
                 "arrive, and pairs past the buffer fail the step")
    DEFINE_float("moe_aux", 0.01, "Load-balance auxiliary loss "
                 "coefficient for --moe_experts")
    DEFINE_integer("moe_top_k", 0, "If > 0, the --moe_experts layer is "
                   "the DROPLESS routed one (ops/moe.py:routed_experts): "
                   "this many experts a token from a softmax over all, "
                   "weights renormalised, rows sorted by expert into a "
                   "buffer of --moe_capacity times the expected rows and "
                   "run through grouped products; no auxiliary loss. "
                   "Needs --mlp_gated. 0 = the top-1 Switch layer")
    DEFINE_integer("moe_ffn_dim", 0, "Width of a routed expert "
                   "(--moe_top_k); 0 = 4 x --d_model")
    DEFINE_integer("moe_first_expert", 0, "First of the experts this "
                   "job HOLDS among the --moe_experts the router chooses "
                   "between (--moe_top_k): the chip's share of an "
                   "expert-parallel deployment; the others add nothing")
    DEFINE_integer("moe_held_experts", 0, "How many experts are held, "
                   "from --moe_first_expert on (0 = all of them)")
    DEFINE_string("norm", "layernorm", "The LM's normalisation: layernorm, "
                  "rmsnorm (no bias leaf) or rmsnorm_zero_centred (RMSNorm "
                  "whose leaf w gives the gain 1 + w, drawn as 0; the "
                  "--qk_norm gains too)")
    DEFINE_float("norm_eps", 1e-5, "Epsilon of --norm (and of --qk_norm)")
    DEFINE_float("rope_theta", 0.0, "If > 0, rotary positions of this "
                 "base on q and k (rotate-half form) in place of the "
                 "learned position table")
    DEFINE_integer("num_kv_heads", 0, "Key/value heads under --num_heads "
                   "query heads (grouped-query attention); must divide "
                   "--num_heads. 0 = as many as query heads")
    DEFINE_integer("head_dim", 0, "Width of an attention head; 0 = "
                   "--d_model / --num_heads")
    DEFINE_boolean("qk_norm", False, "RMSNorm over the head width on q "
                   "and k, before the rotary positions")
    DEFINE_boolean("mlp_gated", False, "Gated feed-forward: silu(gate) * "
                   "up in place of ReLU (the dense MLP and the routed "
                   "experts alike)")
    DEFINE_boolean("biases", True, "Biases on the feed-forward and the "
                   "output head (the attention projections have none)")
    DEFINE_integer("mlp_dim", 0, "Width of the LM's dense feed-forward; "
                   "0 = 4 x --d_model")
    DEFINE_string("layer_plan", "", "Layers that DIFFER: one entry a "
                  "layer, <attention>:<query heads>:<feed-forward> joined "
                  "by commas, attention full (the model's causal mask), "
                  "window (--attn_window keys) or linear (the gated delta "
                  "rule of --linear_key_heads; its heads are value heads), "
                  "feed-forward dense (the "
                  "--mlp_gated MLP of 4 x --d_model) or routed "
                  "(--moe_top_k); e.g. full:48:dense,window:64:routed. "
                  "As many entries as --num_blocks. Empty: every layer "
                  "alike, from the other flags. Local and data-parallel "
                  "training only: the steps that split a model over the "
                  "mesh's model axis, and serving, refuse it")
    DEFINE_integer("attn_window", 0, "Keys a query of a window layer of "
                   "--layer_plan sees: itself and the window - 1 before "
                   "it")
    DEFINE_float("window_rope_theta", 0.0, "Rotary base of --layer_plan's "
                 "window layers, on the whole head width, unscaled; 0 = "
                 "the full layers' --rope_theta, --rope_fraction and "
                 "--rope_yarn")
    DEFINE_float("rope_fraction", 1.0, "Share of the head width that "
                 "--rope_theta rotates (the first dimensions; the rest "
                 "pass through)")
    DEFINE_string("rope_yarn", "", "YaRN scaling of --rope_theta's "
                  "frequencies: factor,original_positions,beta_fast,"
                  "beta_slow,attention_factor (the last multiplies cos "
                  "and sin); empty = none")
    DEFINE_boolean("attn_gate", False, "A sigmoid gate a head and row on "
                   "the attention's output, from the layer's normalised "
                   "input through a (d_model, heads) matrix")
    DEFINE_integer("moe_shared_dim", 0, "If > 0, beside the routed "
                   "experts of --moe_top_k one gated expert of this width "
                   "that every row takes, whole on every chip")
    DEFINE_boolean("moe_shared_gate", False, "A sigmoid gate a row on "
                   "--moe_shared_dim's expert, from the layer's normalised "
                   "input through a (d_model, 1) matrix")
    DEFINE_boolean("attn_gate_elementwise", False, "A sigmoid gate an "
                   "element on the attention's output: q's projection is "
                   "twice as wide, [q ; gate] a head")
    DEFINE_integer("linear_key_heads", 0, "Key (and query) heads of "
                   "--layer_plan's linear layers (Gated DeltaNet: the gated "
                   "delta rule, ops/linear_attention.py); must divide each "
                   "linear layer's value heads")
    DEFINE_integer("linear_key_dim", 0, "Width of a linear layer's key "
                   "head")
    DEFINE_integer("linear_value_dim", 0, "Width of a linear layer's value "
                   "head")
    DEFINE_integer("linear_conv", 0, "Taps of a linear layer's causal "
                   "depthwise convolution over its q, k and v channels")
    DEFINE_string("moe_scoring", "softmax", "How --moe_top_k's router "
                  "turns logits into the scores it ranks and weights by: "
                  "softmax over all experts, or sigmoid of each")
    DEFINE_float("moe_scale", 1.0, "Factor on --moe_top_k's renormalised "
                 "top-k weights")
    DEFINE_integer("loop_passes", 1, "Times the LM's stack of "
                   "--num_blocks layers is run over the SAME weights: "
                   "every pass ends in the final norm, feeds the next, and "
                   "is scored by the one head; an exit gate (one linear "
                   "unit a token) after every pass gives the distribution "
                   "over passes that weighs the passes' losses. 1 = a "
                   "stack run once, no gate. Local and data-parallel "
                   "training only: the steps that split a model over the "
                   "mesh's model axis, and serving, refuse more")
    DEFINE_float("loop_exit_beta", 0.0, "Weight of the exit "
                 "distribution's entropy in --loop_passes' loss: mean over "
                 "tokens of sum_t p(t) CE_t - beta H(p)")
    DEFINE_boolean("sandwich_norm", False, "A second --norm on the OUTPUT "
                   "of the attention half and of the feed-forward half, "
                   "before each is added to the residual stream (two more "
                   "gains a layer)")
    DEFINE_string("objective", "next_token", "The LM's training "
                  "objective: next_token (causal, shifted targets) or "
                  "masked_diffusion (diffusion over blocks of "
                  "--diffusion_block tokens: the step masks each block's "
                  "positions with probability t ~ U(--diffusion_t_min, 1) "
                  "drawn under its own key, the network sees [noised ; "
                  "clean] under the block-diffusion mask, masked positions "
                  "predict their own token weighted 1/t; the mask id is "
                  "--vocab_size - 1, which the data leaves out). Needs "
                  "--device_data")
    DEFINE_integer("diffusion_block", 4, "Block length of --objective "
                   "masked_diffusion; must divide --seq_len")
    DEFINE_float("diffusion_t_min", 1e-3, "Smallest masking probability "
                 "of --objective masked_diffusion, in (0, 1)")
    DEFINE_boolean("expert_parallel", False, "Shard the MoE experts "
                   "--model_axis ways over the mesh's 'model' axis "
                   "(expert parallelism: every device routes "
                   "identically, computes its experts' tokens, one "
                   "psum combines — parallel/expert_parallel.py). "
                   "Requires --moe_experts divisible by --model_axis. "
                   "Composes with --device_data (the resident chunked "
                   "sampler) and --clip_norm (axis-aware)")
    DEFINE_boolean("remat", False, "Rematerialize each transformer block "
                   "in the backward pass (jax.checkpoint): activation "
                   "memory drops to one block's worth at the cost of "
                   "one extra forward — the standard long-context trade. "
                   "Kept besides each block's input: the output and the "
                   "logsumexp of --attn_block's attention (batch x seq x "
                   "d_model of the compute dtype + batch x heads x seq "
                   "f32 a block), so that the extra forward does not run "
                   "the attention again; dense and ring attention keep "
                   "nothing")
    DEFINE_integer("zero", 0, "ZeRO-sharded data parallelism (sync DP "
                   "only, parallel/zero.py): 0 = replicated (default), "
                   "1 = shard the optimizer state 1/D per data rank "
                   "(grads reduce-scatter instead of all-reduce — |G|+|P| "
                   "on the wire vs 2|G| — and one all_gather rebuilds the "
                   "updated params), 3 = FSDP-style (params live sharded "
                   "too, gathered inside forward/backward). Trajectories "
                   "match replicated DP bit-for-bit (last-ulp under "
                   "--clip_norm); checkpoints stay standard-layout, so "
                   "--zero runs and replicated runs restore each other's "
                   "checkpoints. Composes with --device_data, "
                   "--accum_steps, --clip_norm, --augment; mutually "
                   "exclusive with the model-axis strategies "
                   "(--pipeline/--seq_parallel/--expert_parallel/"
                   "--model_axis>1) and ps mode")
    DEFINE_boolean("zero_overlap", False, "ZeRO comm/compute overlap "
                   "(requires --zero 1|3): grads reduce-scatter in "
                   "--zero_bucket_mb buckets that issue as backward "
                   "produces leaves (instead of one serial flat "
                   "scatter at the end), and — at level 3 — the param "
                   "all_gather is prefetched one step ahead inside the "
                   "--device_data scan (double-buffered; XLA's async "
                   "collectives hide it behind compute) and reused by "
                   "forward AND backward — the |G|+|P| wire volume "
                   "leaves the critical path. Trajectories stay "
                   "bit-identical to the serial ZeRO path (same "
                   "padding, same chunk ownership)")
    DEFINE_float("zero_bucket_mb", 4.0, "Bucket size in MB for "
                 "--zero_overlap's bucketed reduce-scatter/all-gather "
                 "(the comm-latency/overlap-granularity knob): leaves "
                 "group in canonical order until a bucket exceeds "
                 "this, one collective per bucket")
    DEFINE_string("prng", "threefry", "PRNG implementation: threefry "
                  "(default, partition-invariant) or rbg (hardware RNG — "
                  "measured ~4% faster steps on TPU; dropout masks and "
                  "on-device batch sampling draw from it). Checkpoints "
                  "store the rng key, whose shape differs between "
                  "implementations: resume with the same --prng")
    DEFINE_string("ps_wire", "f32", "PS-mode transport precision: f32 "
                  "(exact, reference parity) or bf16 — every pulled "
                  "param and pushed grad moves at half width over BOTH "
                  "the TCP wire and the host<->chip link (ps-side master "
                  "params stay f32; same precision class as bf16 compute)")
    DEFINE_boolean("ps_prefetch", True, "PS mode, full-pull cycle only "
                   "(sgd runs the --ps_mirror cycle by default; set "
                   "--ps_mirror=false for this flag to apply): keep one "
                   "parameter pull in flight, overlapping the next pull "
                   "with the chip's gradient computation and the push "
                   "(the pulled snapshot is one own-push staler — "
                   "async-SGD staleness class). false = serial "
                   "pull/compute/push reference cycle")
    DEFINE_boolean("ps_mirror", True, "PS mode: keep a device-resident "
                   "mirror of the params (and, for momentum/adam, the "
                   "optimizer slots) and replay each pushed gradient's "
                   "identical ps-side update ON CHIP instead of re-"
                   "pulling + re-uploading the full parameter set every "
                   "cycle (the dominant transfer). The mirror resyncs "
                   "params (+slots) from the ps every --ps_resync_steps "
                   "and immediately when another worker's push is "
                   "detected (the returned global step skips ahead); "
                   "=false restores the pull cycle --ps_prefetch "
                   "controls")
    DEFINE_integer("ps_resync_steps", 50, "Steps between full parameter "
                   "resyncs in --ps_mirror mode (bounds any numeric drift "
                   "between the ps-side and device-side sgd applies)")
    DEFINE_integer("coord_steps", COORD_STEPS_DEFAULT,
                   "Multi-host coordination cadence in "
                   "steps: sync-mode processes agree on stop/checkpoint "
                   "decisions with one tiny allgather every this many "
                   "steps (worst-case stop latency = this many extra "
                   "steps). Single-process runs never vote")
    DEFINE_boolean("sharded_checkpoint", True, "Cross-host-sharded state "
                   "checkpoints as per-process shard files (each host "
                   "writes its locally-owned slices; NO allgather — the "
                   "save moves 1/P of the model per host instead of "
                   "O(model) to every host). Restore reassembles from "
                   "the complete set; --eval_only and the inspect CLI "
                   "read both formats. =false keeps the monolithic "
                   "single-file format. Locally-fetchable state always "
                   "writes the monolithic file")
    DEFINE_boolean("async_checkpoint", True, "Write cadenced checkpoints "
                   "from a background thread (the state is fetched to "
                   "host on the training thread, then serialized and "
                   "written off-thread; training never blocks on the "
                   "disk). The final checkpoint on exit is always "
                   "synchronous")
    DEFINE_string("fault_spec", "", "Deterministic fault injection "
                  "(utils/faults.py): comma-separated rules, each "
                  "point[:key=value]... — e.g. "
                  "'ckpt_write:at_step=40:mode=crash', "
                  "'restore:mode=torn_file', 'init:mode=refuse:times=2'. "
                  "Empty (default) injects nothing and leaves every path "
                  "byte-identical in behavior; the DTT_FAULT_SPEC env "
                  "var is the fallback for subprocesses. "
                  "'python tools/trace_ops.py --faults' lists the points")
    DEFINE_integer("init_retries", 8, "Bounded retries around "
                   "jax.distributed.initialize for a worker relaunched "
                   "after a crash (the coordinator may still be coming "
                   "back); linear backoff of --init_backoff_s per "
                   "attempt, loud failure when exhausted. 0 = fail on "
                   "the first refusal (the pre-recovery behavior)")
    DEFINE_float("init_backoff_s", 2.0, "Backoff unit (seconds) between "
                 "--init_retries attempts; attempt k waits k*this, "
                 "capped at 30s")
    DEFINE_float("init_timeout_s", 0.0, "Per-attempt cap (seconds) on "
                 "jax.distributed.initialize's own connection wait "
                 "(0 = the library default, 300s); lower it so "
                 "--init_retries attempts turn over quickly in "
                 "fast-relaunch deployments")
    DEFINE_boolean("telemetry", True, "The always-on observability "
                   "spine (utils/telemetry.py): span tracing into "
                   "<logdir>/spans-<host>.jsonl (Chrome-trace export "
                   "via tools/trace_view.py), step-time breakdown "
                   "scalars (step_host_wait_s/step_dispatch_s/"
                   "step_device_s) next to the throughput numbers, and "
                   "the crash flight recorder "
                   "(<logdir>/flightrec-<host>.jsonl). Overhead is "
                   "bench-asserted (< 5 us/span, < 2% on the flagship "
                   "step); =false disables recording entirely. "
                   "--profile_dir/--serve_profile_batches remain the "
                   "deep-dive (one-shot jax.profiler) path")
    DEFINE_float("watchdog_s", 0.0, "If > 0, arm a hang watchdog "
                 "around every device dispatch and collective: an "
                 "operation still incomplete after this many seconds "
                 "dumps all-thread stacks (faulthandler), the last "
                 "spans, and the in-flight op's context to stderr and "
                 "the flight recorder — turning a silent collective-"
                 "rendezvous deadlock into a diagnosable report. "
                 "0 = off. Set it well above a legitimate step/compile "
                 "time (first-step XLA compiles are armed too)")
    DEFINE_boolean("watchdog_abort", False, "After a watchdog report, "
                   "hard-exit the process (status 124) instead of "
                   "continuing to wait — the unattended-run setting "
                   "(an orchestrator relaunches; the report survives "
                   "in the flight recorder). Requires --watchdog_s > 0")
    DEFINE_integer("flightrec_events", 512, "Flight-recorder ring "
                   "length: how many recent spans/scalars/notes the "
                   "crash postmortem (flightrec-<host>.jsonl) holds")
    DEFINE_boolean("mfu", True, "Efficiency accounting "
                   "(utils/efficiency.py): emit mfu, "
                   "model_flops_per_sec and goodput scalars next to "
                   "images_per_sec at the display cadence in every "
                   "training loop. The FLOPs budget is analytic "
                   "(per-layer, no chip interaction); goodput charges "
                   "restore/checkpoint/eval/compile stalls against the "
                   "wall clock. =false drops the scalars entirely")
    DEFINE_float("mfu_peak_flops", 0.0, "Per-chip peak FLOP/s the MFU "
                 "denominator uses. 0 = auto: known TPU chips resolve "
                 "from a spec table by device_kind; anything else runs "
                 "a one-shot cached matmul calibration (achieved "
                 "FLOP/s stands in for peak). Set explicitly when the "
                 "auto answer is wrong for your part")
    DEFINE_string("sentinel_action", "", "Training-health sentinels "
                  "(utils/sentinel.py): '' (default) = unarmed; "
                  "warn = report trips (loud print, sentinel:<kind> "
                  "span, scalar, flight-recorder dump); snapshot = "
                  "warn + an emergency checkpoint of the last "
                  "known-good state through the verified-save path "
                  "into <logdir>/sentinel/; abort = snapshot + raise "
                  "so the run exits loudly. Checks run at the display "
                  "cadence on the scalars the loop already computes — "
                  "no extra device work")
    DEFINE_string("sentinel_kinds", "nan,loss_spike,grad_explosion,"
                  "throughput_collapse",
                  "Comma-separated sentinel kinds to arm (subset of "
                  "nan, loss_spike, grad_explosion, "
                  "throughput_collapse)")
    DEFINE_integer("sentinel_window", 32, "Rolling-history length (in "
                   "display-cadence observations) behind the sentinel "
                   "median/MAD baselines")
    DEFINE_float("sentinel_threshold", 10.0, "MADs above the rolling "
                 "median at which loss_spike/grad_explosion trip")
    DEFINE_integer("hbm_sample_every", 1, "Live HBM accounting "
                   "(utils/resources.MemoryMeter): sample "
                   "device.memory_stats() every this many display "
                   "boundaries and emit hbm_in_use_bytes/hbm_peak_bytes/"
                   "hbm_headroom_pct scalars next to images_per_sec "
                   "(backends without the stat fall back to live-array "
                   "bytes, labeled; headroom is -1 without a reported "
                   "limit). Samples ride the EXISTING display cadence — "
                   "no new sync points — and land as hbm_sample spans "
                   "for fleet_report/the OOM postmortem. 0 = off. "
                   "Rides the telemetry spine (--telemetry=false "
                   "disables it)")
    DEFINE_boolean("elastic", False, "Elastic, preemption-tolerant "
                   "training (training/elastic.py): on a membership "
                   "change — a spot preemption modeled by the "
                   "'preempt' fault point, or a departure bit on the "
                   "multi-host coordinator vote — the run drains to "
                   "the next checkpoint boundary (a verified-save "
                   "drain checkpoint; an 'immediate' preemption loses "
                   "the step and falls back to the last checkpoint or "
                   "the sentinel's emergency snapshot), re-forms the "
                   "mesh at the new world size, restores the standard-"
                   "layout checkpoint into the rescaled DP/ZeRO "
                   "layout, and continues — bitwise on the trajectory "
                   "a fresh run restored at the target shape would "
                   "take. The resize downtime lands as the goodput "
                   "ledger's named resize_s charge plus "
                   "membership_change/resize spans. Auto-armed "
                   "whenever --fault_spec names the preempt point")
    DEFINE_integer("world_size", 0, "Launch-world size for elastic "
                   "training: cap the run to this many world members "
                   "(single-process: local devices — the device-host "
                   "topology; multi-process: processes). 0 = the full "
                   "device/process set. A smaller launch world leaves "
                   "headroom for a resize to GROW into (the re-add "
                   "half of the elastic story)")
    DEFINE_integer("recompile_budget", 0, "Recompilation sentry "
                   "(utils/resources.CompileSentry): if > 0, more than "
                   "this many traced-signature recompiles inside a "
                   "rolling 60 s window trips a storm report naming "
                   "the churned shape/dtype delta (loud print, "
                   "recompile_storm span, flight-recorder dump). "
                   "0 = count only: the compiles_total/compile_time_s/"
                   "recompiles_total scalars are always emitted while "
                   "telemetry is on")
    FLAGS._register_validator(_validate_core_flags)
    FLAGS._register_validator(_validate_model_data_flags)
    FLAGS._register_validator(_validate_lm_arch_flags)
    FLAGS._register_validator(_validate_layer_plan_flags)
    FLAGS._register_validator(_validate_loop_flags)
    FLAGS._register_validator(_validate_pairing_flags)
    FLAGS._register_validator(_validate_pipeline_flags)
    FLAGS._register_validator(_validate_elastic_flags)
    FLAGS._register_validator(_validate_zero_flags)
    FLAGS._register_validator(_validate_fault_spec)
    FLAGS._register_validator(_validate_telemetry_flags)
    FLAGS._register_validator(_validate_efficiency_flags)
    FLAGS._register_validator(_validate_resource_flags)
    define_serving_flags()


def define_serving_flags():
    """The serving CLI surface (``python -m
    distributed_tensorflow_tpu.serving``); idempotent, and also defined
    for the training CLI so one launch-script flag namespace covers the
    whole lifecycle."""
    if "serve_port" in FLAGS._defs:
        return
    DEFINE_string("serve_host", "127.0.0.1", "Bind address for the "
                  "serving HTTP front end")
    DEFINE_integer("serve_port", 8000, "Port for the serving HTTP front "
                   "end (0 = ephemeral)")
    DEFINE_integer("serve_max_batch", 8, "Largest microbatch the dynamic "
                   "batcher assembles; must be a power of two (batches "
                   "pad to power-of-two buckets so the jitted-executable "
                   "cache stays one entry per bucket)")
    DEFINE_float("serve_max_delay_ms", 5.0, "Longest the batcher holds "
                 "the oldest queued request while waiting to fill a "
                 "batch — the latency/throughput knob")
    DEFINE_integer("serve_queue_depth", 64, "Bounded request queue; a "
                   "full queue REJECTS new requests immediately "
                   "(backpressure with a reason, never a hang). Must "
                   "hold at least one full --serve_max_batch")
    DEFINE_float("serve_timeout_ms", 1000.0, "Default per-request "
                 "deadline: a request still queued past it completes "
                 "with a deadline rejection instead of burning chip "
                 "time on an answer nobody awaits")
    DEFINE_integer("serve_max_new_tokens", 32, "Default (and cap) for "
                   "generate requests' new-token budget; prompt + "
                   "budget must fit the model's context window")
    DEFINE_float("serve_temperature", 0.0, "Default sampling "
                 "temperature for generate requests (0 = greedy)")
    DEFINE_float("serve_reload_secs", 10.0, "Checkpoint-watcher poll "
                 "cadence: a newer step in --logdir hot-swaps into the "
                 "engine between microbatches (0 = watching off)")
    DEFINE_integer("serve_profile_batches", 0, "If > 0, capture one "
                   "jax.profiler trace around this many served batches "
                   "and log the artifact path (utils/profiling."
                   "ServeTraceCapture)")
    DEFINE_string("serve_profile_dir", "", "Trace directory for "
                  "--serve_profile_batches (default: <logdir>/"
                  "serve_profile)")
    DEFINE_integer("serve_tp", 1, "Tensor-parallel ways for serving "
                   "placement over the mesh's 'model' axis (Megatron "
                   "block split via parallel/tensor_parallel); 1 = "
                   "DP-replicated params. Must divide --num_heads and "
                   "the MLP width")
    DEFINE_integer("serve_metrics_every", 50, "Emit serving scalars "
                   "(queue depth, p50/p99 latency, throughput, reload "
                   "counters) every this many microbatches (0 = off)")
    DEFINE_float("serve_hbm_headroom_pct", 0.0, "Replica-drain floor: "
                 "/healthz flips to 503 (ok=false, hbm_low_headroom) "
                 "when the replica's live HBM headroom drops below "
                 "this percent of the device limit — a router can "
                 "drain a leaking replica before the allocator kills "
                 "it mid-request. 0 = off. Only meaningful where the "
                 "backend reports a memory limit (headroom reads -1 "
                 "elsewhere and never trips the floor)")
    DEFINE_float("slo_p99_ms", 0.0, "Serving latency SLO (the request "
                 "plane, serving/reqtrace.py): a request is compliant "
                 "when it completes ok within this many milliseconds. "
                 "Arms the error-budget ledger — the /metrics slo "
                 "block (compliant_pct, budget_remaining, fast/slow "
                 "burn rates) and the /healthz 503 on a fast-burn "
                 "breach (joining the HBM-headroom drain floor). "
                 "0 = SLO accounting off (phase timelines and tail "
                 "attribution still run)")
    DEFINE_float("slo_target_pct", 99.0, "The SLO compliance target: "
                 "this percent of requests are promised within "
                 "--slo_p99_ms; the remainder is the error budget the "
                 "burn rates are measured against. Must be in "
                 "(50, 100]; only meaningful with --slo_p99_ms > 0")
    DEFINE_integer("reqtrace_ring", _REQTRACE_RING_DEFAULT,
                   "Bounded per-request audit "
                   "ring (the request plane): how many finished "
                   "request summaries — id, route, shape-bucket, "
                   "disposition, phase breakdown — the replica retains "
                   "for the /metrics tail exemplars and postmortems")
    DEFINE_integer("reqtrace_exemplars", _REQTRACE_EXEMPLARS_DEFAULT,
                   "How many worst live "
                   "exemplars (request_id + phase breakdown, by total "
                   "latency) the /metrics tail block names; must be "
                   "in [1, 64]")
    DEFINE_string("serve_scheduler", "whole_batch", "Generate-route "
                  "scheduler: 'whole_batch' (DynamicBatcher — one "
                  "microbatch committed for its entire generation) or "
                  "'continuous' (iteration-level slot scheduler over a "
                  "paged KV cache, serving/continuous.py — requests "
                  "admit/retire between decode steps, greedy outputs "
                  "bitwise identical to whole_batch). Continuous "
                  "serves --model lm, one replica per device (no "
                  "--serve_tp)")
    DEFINE_integer("serve_slots", 4, "Continuous scheduler: fixed "
                   "number of batch slots (concurrent in-flight "
                   "generations). Must be >= 2 — slot width >= 2 keeps "
                   "the decode contractions on the GEMM kernel, the "
                   "same bitwise-parity floor the whole-batch decode "
                   "enforces")
    DEFINE_integer("serve_kv_page", 16, "Continuous scheduler: tokens "
                   "per KV-cache page; must divide --seq_len (a slot's "
                   "logical pages tile the context window exactly)")
    DEFINE_integer("serve_kv_pages", 0, "Continuous scheduler: physical "
                   "KV pages in the pool. 0 = full provisioning "
                   "(serve_slots * seq_len / serve_kv_page — every slot "
                   "can hold a max-length request); smaller pools "
                   "oversubscribe slots against pages and admission "
                   "gates on the page commitment. Must hold at least "
                   "one full-context request (seq_len / serve_kv_page)")
    DEFINE_string("router_replicas", "", "Fleet router (serving/"
                  "router.py): comma-separated host:port replica list "
                  "the router fans traffic over (empty = router off; "
                  "required by python -m distributed_tensorflow_tpu."
                  "serving.router)")
    DEFINE_string("router_host", "127.0.0.1", "Bind address for the "
                  "router HTTP front end")
    DEFINE_integer("router_port", 8100, "Port for the router HTTP "
                   "front end (0 = ephemeral)")
    DEFINE_float("router_poll_ms", 200.0, "Health-poller cadence: each "
                 "tick folds every replica's /healthz (and every k-th "
                 "tick /metrics) into its router-side state. Must be "
                 "in [10, 60000]")
    DEFINE_integer("router_retries", 2, "Max per-request retry "
                   "attempts after the first dispatch, on connect-fail "
                   "or 5xx only (4xx/429 pass through). Must be in "
                   "[0, 10]")
    DEFINE_float("router_backoff_ms", 20.0, "Base retry backoff "
                 "(exponential with full jitter: base * 2^(n-1) * "
                 "U[0.5, 1]). Must be in [0, 10000]")
    DEFINE_float("router_retry_budget_pct", 10.0, "Global retry budget "
                 "as a percent of observed requests (plus a small "
                 "burst floor) — a fleet outage cannot amplify into a "
                 "retry storm. Must be in [0, 100]")
    DEFINE_float("router_hedge_ms", 0.0, "Latency budget after which a "
                 "still-unresolved request fires ONE hedged duplicate "
                 "onto a different replica; first success wins and the "
                 "SLO ledger books one outcome per request id. "
                 "0 = hedging off. Requires --telemetry (the hedge "
                 "race is audited through route_hedge spans)")
    DEFINE_float("router_hedge_budget_pct", 5.0, "Hedge volume cap as "
                 "a percent of observed requests. Must be in [0, 100]")
    DEFINE_integer("router_breaker_fails", 3, "Circuit breaker: "
                   "consecutive dispatch/poll failures that eject a "
                   "replica. Must be in [1, 100]")
    DEFINE_float("router_eject_s", 1.0, "Ejection cooldown before the "
                 "half-open probe (doubling per consecutive "
                 "re-ejection, capped 8x). Must be in (0, 3600]")
    DEFINE_integer("router_min_healthy", 1, "Rolling reload / fleet "
                   "health floor: the healthy-replica count the router "
                   "never lets orchestration drop below. Must be >= 0 "
                   "and, with --router_replicas set, < the replica "
                   "count (draining one replica must stay legal)")
    FLAGS._register_validator(_validate_serving_flags)
    FLAGS._register_validator(_validate_reqtrace_flags)
    FLAGS._register_validator(_validate_router_flags)


def _require(values: dict, name: str, check, what: str):
    """One bounds check: skip when the flag is absent from this parse
    set (partial namespaces), raise with the flag and the bound NAMED
    otherwise — the dttlint DTT006 contract (every flag is either read
    by a registered validator or carries an explicit baseline entry)."""
    v = values.get(name)
    if v is not None and not check(v):
        raise ValueError(f"--{name}={v} {what}")


def _validate_core_flags(values: dict):
    """Parse-time bounds for the reference surface + the loop-numeric
    extensions (the PR-2 _register_validator pattern, swept over the
    whole flag table by dttlint DTT006): a zero step budget, a
    non-positive learning rate, or a dead display cadence surfaces at
    the command line, not as a silently-empty run. Range checks ONLY —
    cross-flag pairings live in _validate_pairing_flags (r18) or, where
    the tests pin a train()-time message (e.g. --accum_steps vs
    --device_data), stay library errors."""
    _require(values, "training_iter", lambda v: int(v) >= 1,
             "must be >= 1 (the step budget)")
    _require(values, "learning_rate", lambda v: float(v) > 0,
             "must be > 0")
    _require(values, "display_step", lambda v: int(v) >= 1,
             "must be >= 1 (the display/eval cadence)")
    _require(values, "task_index", lambda v: int(v) >= 0,
             "must be >= 0 (a cluster-member index)")
    _require(values, "hidden_units", lambda v: int(v) >= 1,
             "must be >= 1")
    _require(values, "keep_prob", lambda v: 0 < float(v) <= 1,
             "must be in (0, 1] (a dropout KEEP probability)")
    _require(values, "weight_decay", lambda v: float(v) >= 0,
             "must be >= 0")
    _require(values, "clip_norm", lambda v: float(v) >= 0,
             "must be >= 0 (0 = no clipping)")
    _require(values, "save_model_secs", lambda v: int(v) >= 0,
             "must be >= 0 (0 = checkpoint every boundary)")
    _require(values, "max_to_keep", lambda v: int(v) >= 1,
             "must be >= 1 (GC must keep at least the newest)")
    _require(values, "seed", lambda v: int(v) >= 0,
             "must be >= 0 (PRNG keys are unsigned)")
    _require(values, "eval_step", lambda v: int(v) >= 0,
             "must be >= 0 (0 = end-of-run eval only)")
    _require(values, "validation_size", lambda v: int(v) >= 0,
             "must be >= 0 (0 = no held-out split)")
    _require(values, "accum_steps", lambda v: int(v) >= 1,
             "must be >= 1 (microbatches per update)")
    _require(values, "device_chunk", lambda v: int(v) >= 1,
             "must be >= 1 (steps per compiled scan chunk)")
    _require(values, "coord_steps", lambda v: int(v) >= 1,
             "must be >= 1 (the multi-host vote cadence)")
    _require(values, "profile_steps", lambda v: int(v) >= 1,
             "must be >= 1 (the profiler window)")
    _require(values, "init_retries", lambda v: int(v) >= 0,
             "must be >= 0 (0 = fail on the first refusal)")
    _require(values, "init_backoff_s", lambda v: float(v) >= 0,
             "must be >= 0 seconds")
    _require(values, "init_timeout_s", lambda v: float(v) >= 0,
             "must be >= 0 seconds (0 = the library default)")
    _require(values, "ps_resync_steps", lambda v: int(v) >= 1,
             "must be >= 1 (the mirror resync cadence)")
    mode = values.get("mode")
    if mode is not None and mode not in ("auto", "local", "sync", "ps"):
        raise ValueError(f"--mode={mode!r} must be one of auto, local, "
                         f"sync, ps")


def _validate_model_data_flags(values: dict):
    """Parse-time domain checks for the model/data surface: an unknown
    model/dataset/optimizer/schedule/prng name, or an impossible LM
    shape, surfaces at the command line with the whitelist named —
    instead of a KeyError minutes later from the registry."""
    model = values.get("model")
    if model is not None:
        # importing the package runs the @register_model decorators —
        # the whitelist IS the registry, no second list to drift. The
        # import is guarded: flag PARSING must stay possible when the
        # jax backend is broken (the outage class bench's degraded
        # records exist for); get_model re-raises loudly on use.
        try:
            import distributed_tensorflow_tpu.models  # noqa: F401
            from distributed_tensorflow_tpu.models.registry import (
                available_models,
            )
        except Exception:
            available_models = None
        if available_models is not None and \
                model not in available_models():
            raise ValueError(f"--model={model!r} must be one of "
                             f"{', '.join(available_models())}")
    dataset = values.get("dataset")
    if dataset is not None and dataset not in (
            "mnist", "fashion_mnist", "cifar10", "lm"):
        raise ValueError(f"--dataset={dataset!r} must be one of mnist, "
                         f"fashion_mnist, cifar10, lm")
    opt = values.get("optimizer")
    if opt is not None and opt not in ("sgd", "momentum", "adam"):
        raise ValueError(f"--optimizer={opt!r} must be one of sgd, "
                         f"momentum, adam")
    sched = values.get("lr_schedule")
    if sched is not None and sched not in (
            "constant", "cosine", "linear", "exponential"):
        raise ValueError(f"--lr_schedule={sched!r} must be one of "
                         f"constant, cosine, linear, exponential")
    prng = values.get("prng")
    if prng is not None and prng not in (
            "threefry", "threefry2x32", "rbg", "unsafe_rbg"):
        raise ValueError(f"--prng={prng!r} must be one of threefry, "
                         f"threefry2x32, rbg, unsafe_rbg")
    wire = values.get("ps_wire")
    if wire is not None and wire not in ("f32", "bf16"):
        raise ValueError(f"--ps_wire={wire!r} must be f32 or bf16")
    _require(values, "warmup_steps", lambda v: int(v) >= 0,
             "must be >= 0 (0 = no warmup)")
    _require(values, "decay_steps", lambda v: int(v) >= 0,
             "must be >= 0 (0 = the full step budget)")
    _require(values, "decay_rate", lambda v: float(v) > 0,
             "must be > 0 (a decay factor)")
    _require(values, "augment_pad", lambda v: int(v) >= 0,
             "must be >= 0 (crop padding)")
    _require(values, "seq_len", lambda v: int(v) >= 2,
             "must be >= 2 (targets are the sequence shifted one token)")
    _require(values, "vocab_size", lambda v: int(v) >= 2,
             "must be >= 2")
    _require(values, "attn_block", lambda v: int(v) >= 0,
             "must be >= 0 (0 = dense attention)")
    _require(values, "ce_block", lambda v: int(v) >= 0,
             "must be >= 0 (0 = dense loss head)")
    _require(values, "moe_experts", lambda v: int(v) >= 0,
             "must be >= 0 (0 = dense MLPs)")
    _require(values, "moe_capacity", lambda v: float(v) > 0,
             "must be > 0 (a per-expert capacity factor)")
    _require(values, "moe_aux", lambda v: float(v) >= 0,
             "must be >= 0 (the load-balance coefficient)")


def _validate_lm_arch_flags(values: dict):
    """The LM's further choices: each flag's own range, then the pairs
    that would be silently inert or cannot be built."""
    from distributed_tensorflow_tpu.models.transformer import NORMS

    _require(values, "moe_top_k", lambda v: int(v) >= 0,
             "must be >= 0 (0 = the top-1 Switch layer)")
    _require(values, "moe_ffn_dim", lambda v: int(v) >= 0,
             "must be >= 0 (0 = 4 x d_model)")
    _require(values, "moe_first_expert", lambda v: int(v) >= 0,
             "must be >= 0 (an index among --moe_experts)")
    _require(values, "moe_held_experts", lambda v: int(v) >= 0,
             "must be >= 0 (0 = every expert)")
    _require(values, "norm", lambda v: v in NORMS,
             f"must be one of {', '.join(NORMS)}")
    _require(values, "norm_eps", lambda v: float(v) > 0, "must be > 0")
    _require(values, "rope_theta", lambda v: float(v) >= 0,
             "must be >= 0 (0 = learned positions)")
    _require(values, "num_kv_heads", lambda v: int(v) >= 0,
             "must be >= 0 (0 = as many as query heads)")
    _require(values, "head_dim", lambda v: int(v) >= 0 and int(v) % 2 == 0,
             "must be even and >= 0 (0 = d_model / num_heads)")
    _require(values, "qk_norm", lambda v: isinstance(v, bool),
             "must be a boolean")
    _require(values, "mlp_gated", lambda v: isinstance(v, bool),
             "must be a boolean")
    _require(values, "biases", lambda v: isinstance(v, bool),
             "must be a boolean")
    _require(values, "mlp_dim", lambda v: int(v) >= 0,
             "must be >= 0 (0 = 4 x d_model)")
    if values.get("mlp_dim"):
        for other in ("seq_parallel", "expert_parallel"):
            if values.get(other):
                raise ValueError(
                    f"--{other} rebuilds the model with a feed-forward of "
                    f"4 x --d_model: --mlp_dim would silently change "
                    f"nothing there — drop one")
    _require(values, "objective",
             lambda v: v in ("next_token", "masked_diffusion"),
             "must be next_token or masked_diffusion")
    _require(values, "diffusion_block", lambda v: int(v) >= 1,
             "must be >= 1")
    _require(values, "diffusion_t_min", lambda v: 0 < float(v) < 1,
             "must lie in (0, 1)")
    heads, kv = values.get("num_heads"), values.get("num_kv_heads")
    if heads and kv and int(heads) % int(kv):
        raise ValueError(f"--num_kv_heads={kv} must divide --num_heads="
                         f"{heads} (a group of query heads reads one "
                         f"key/value head)")
    top_k, experts = values.get("moe_top_k"), values.get("moe_experts")
    if top_k:
        held = int(values.get("moe_held_experts") or 0) or int(experts or 0)
        first = int(values.get("moe_first_expert") or 0)
        if not experts or int(top_k) > int(experts):
            raise ValueError(f"--moe_top_k={top_k} needs --moe_experts >= "
                             f"{top_k} to choose among")
        if first + held > int(experts):
            raise ValueError(
                f"--moe_first_expert={first} + --moe_held_experts={held} "
                f"reach past the router's --moe_experts={experts}")
        if not values.get("mlp_gated"):
            raise ValueError("--moe_top_k's experts are gated (silu(gate) "
                             "* up): add --mlp_gated")
        if values.get("expert_parallel"):
            raise ValueError(
                "--moe_top_k's layer is told which experts it holds "
                "(--moe_first_expert, --moe_held_experts); "
                "--expert_parallel shards the Switch layer over a mesh "
                "axis — drop one")
    elif any(values.get(n) for n in ("moe_ffn_dim", "moe_first_expert",
                                     "moe_held_experts")):
        raise ValueError("--moe_ffn_dim, --moe_first_expert and "
                         "--moe_held_experts shape the routed layer: "
                         "without --moe_top_k they would silently change "
                         "nothing")
    if values.get("objective") == "masked_diffusion":
        seq, blk = values.get("seq_len"), values.get("diffusion_block")
        if seq and blk and int(seq) % int(blk):
            raise ValueError(f"--diffusion_block={blk} must divide "
                             f"--seq_len={seq}")
        if values.get("device_data") is False or values.get("dataset") \
                not in (None, "lm"):
            raise ValueError(
                "--objective masked_diffusion draws its noise inside the "
                "compiled step of --device_data on --dataset lm; the "
                "host-fed steps have no key for it")
        for other in ("seq_parallel", "pipeline", "zero", "expert_parallel"):
            if values.get(other):
                raise ValueError(
                    f"--objective masked_diffusion runs in the local and "
                    f"sync data-parallel device steps; --{other} builds "
                    f"another step that draws no noise")


def _validate_layer_plan_flags(values: dict):
    """--layer_plan and the flags of the mechanisms it names: each one's
    own range, the pairs that would be inert, and the steps that run one
    kind of layer and so refuse a plan."""
    from distributed_tensorflow_tpu.models.transformer import (
        parse_layer_plan,
        parse_rope_yarn,
    )
    from distributed_tensorflow_tpu.ops.moe import SCORINGS

    _require(values, "attn_window", lambda v: int(v) >= 0,
             "must be >= 0 (0 = no window layers)")
    _require(values, "window_rope_theta", lambda v: float(v) >= 0,
             "must be >= 0 (0 = the full layers' rotary form)")
    _require(values, "rope_fraction", lambda v: 0 < float(v) <= 1,
             "must lie in (0, 1]")
    _require(values, "attn_gate", lambda v: isinstance(v, bool),
             "must be a boolean")
    _require(values, "moe_shared_dim", lambda v: int(v) >= 0,
             "must be >= 0 (0 = no shared expert)")
    _require(values, "moe_scoring", lambda v: v in SCORINGS,
             f"must be one of {', '.join(SCORINGS)}")
    _require(values, "moe_scale", lambda v: float(v) > 0, "must be > 0")
    _require(values, "moe_shared_gate", lambda v: isinstance(v, bool),
             "must be a boolean")
    _require(values, "attn_gate_elementwise", lambda v: isinstance(v, bool),
             "must be a boolean")
    _require(values, "linear_key_heads", lambda v: int(v) >= 0,
             "must be >= 0 (0 = no linear layers)")
    _require(values, "linear_key_dim", lambda v: int(v) >= 0,
             "must be >= 0 (0 = no linear layers)")
    _require(values, "linear_value_dim", lambda v: int(v) >= 0,
             "must be >= 0 (0 = no linear layers)")
    _require(values, "linear_conv", lambda v: int(v) >= 0,
             "must be >= 0 (0 = no linear layers)")
    linear = ("linear_key_heads", "linear_key_dim", "linear_value_dim",
              "linear_conv")
    if values.get("moe_shared_gate") and not values.get("moe_shared_dim"):
        raise ValueError("--moe_shared_gate gates --moe_shared_dim's expert: "
                         "without it it would silently change nothing")
    if values.get("attn_gate") and values.get("attn_gate_elementwise"):
        raise ValueError("--attn_gate (a gate a head) and "
                         "--attn_gate_elementwise (a gate an element) are two "
                         "forms of one gate — drop one")
    parse_rope_yarn(values.get("rope_yarn") or "")
    fraction, head_dim = values.get("rope_fraction"), values.get("head_dim")
    if fraction and head_dim and (float(fraction) * int(head_dim)) % 2:
        raise ValueError(f"--rope_fraction={fraction} of --head_dim="
                         f"{head_dim} must be an even number of dimensions")
    if not values.get("rope_theta") and (
            values.get("rope_yarn") or values.get("window_rope_theta")
            or float(values.get("rope_fraction") or 1.0) != 1.0):
        raise ValueError("--rope_fraction, --rope_yarn and "
                         "--window_rope_theta shape rotary positions: "
                         "without --rope_theta they would silently change "
                         "nothing")
    if not values.get("moe_top_k") and (
            values.get("moe_shared_dim")
            or values.get("moe_scoring") not in (None, "softmax")
            or float(values.get("moe_scale") or 1.0) != 1.0):
        raise ValueError("--moe_shared_dim, --moe_scoring and --moe_scale "
                         "shape the routed layer: without --moe_top_k they "
                         "would silently change nothing")
    plan = values.get("layer_plan")
    some_linear = any(values.get(n) for n in linear)
    if not plan:
        if values.get("attn_window") or values.get("window_rope_theta"):
            raise ValueError("--attn_window and --window_rope_theta are "
                             "the window layers' of --layer_plan: without "
                             "it they would silently change nothing")
        if some_linear:
            raise ValueError("--linear_key_heads, --linear_key_dim, "
                             "--linear_value_dim and --linear_conv are the "
                             "linear layers' of --layer_plan: without it "
                             "they would silently change nothing")
        return
    entries = parse_layer_plan(plan)
    blocks = values.get("num_blocks")
    if blocks is not None and len(entries) != int(blocks):
        raise ValueError(f"--layer_plan names {len(entries)} layers, "
                         f"--num_blocks={blocks}")
    kv = int(values.get("num_kv_heads") or 0)
    key_heads = int(values.get("linear_key_heads") or 0)
    for attention, heads, ffn in entries:
        if attention == "linear":
            if not all(values.get(n) for n in linear):
                raise ValueError("--layer_plan names a linear layer: add "
                                 "--linear_key_heads, --linear_key_dim, "
                                 "--linear_value_dim and --linear_conv")
            if heads % key_heads:
                raise ValueError(f"--layer_plan: a linear layer's {heads} "
                                 f"value heads do not divide over "
                                 f"--linear_key_heads={key_heads}")
        elif kv and heads % kv:
            raise ValueError(f"--layer_plan: {heads} query heads do not "
                             f"divide over --num_kv_heads={kv}")
        if attention == "window" and not values.get("attn_window"):
            raise ValueError("--layer_plan names a window layer: add "
                             "--attn_window")
        if ffn == "routed" and not values.get("moe_top_k"):
            raise ValueError("--layer_plan names a routed layer: add "
                             "--moe_top_k (and --moe_experts)")
    if values.get("moe_top_k") and not any(e[2] == "routed" for e in entries):
        raise ValueError("--moe_top_k is set and --layer_plan names no "
                         "routed layer")
    if values.get("attn_window") and not any(
            e[0] == "window" for e in entries):
        raise ValueError("--attn_window is set and --layer_plan names no "
                         "window layer")
    if some_linear and not any(e[0] == "linear" for e in entries):
        raise ValueError("--linear_key_heads and the other --linear_* flags "
                         "are set and --layer_plan names no linear layer")
    if values.get("objective") not in (None, "next_token"):
        raise ValueError("--layer_plan runs under --objective next_token")
    for other in ("seq_parallel", "pipeline", "expert_parallel"):
        if values.get(other):
            raise ValueError(
                f"--layer_plan runs in the local and data-parallel "
                f"steps; --{other} builds a step whose layers are one "
                f"kind — drop one")
    if int(values.get("model_axis") or 1) > 1:
        raise ValueError("--layer_plan's layers have head counts of their "
                         "own; --model_axis > 1 (tensor parallelism) "
                         "splits one head count — drop one")


def _validate_loop_flags(values: dict):
    """--loop_passes, --loop_exit_beta and --sandwich_norm: each one's own
    range, the pair that would be inert, and the steps and layers that a
    stack run several times does not reach yet."""
    _require(values, "loop_passes", lambda v: int(v) >= 1,
             "must be >= 1 (1 = the stack run once)")
    _require(values, "loop_exit_beta", lambda v: float(v) >= 0,
             "must be >= 0 (0 = no entropy term)")
    _require(values, "sandwich_norm", lambda v: isinstance(v, bool),
             "must be a boolean")
    looped = int(values.get("loop_passes") or 1) > 1
    if values.get("loop_exit_beta") and not looped:
        raise ValueError("--loop_exit_beta weighs the entropy of the exit "
                         "distribution over passes: without --loop_passes "
                         "> 1 it would silently change nothing")
    if (looped or values.get("sandwich_norm")) and values.get("moe_experts"):
        raise ValueError("--loop_passes > 1 and --sandwich_norm are the "
                         "dense feed-forward layers'; --moe_experts makes "
                         "them mixtures — drop one")
    if not looped:
        return
    if values.get("objective") not in (None, "next_token"):
        raise ValueError("--loop_passes > 1 runs under --objective "
                         "next_token")
    for other in ("seq_parallel", "pipeline"):
        if values.get(other):
            raise ValueError(
                f"--loop_passes > 1 runs in the local and data-parallel "
                f"steps; --{other} builds a step that walks the layers "
                f"once (a pipeline's last stage would feed its first) — "
                f"drop one")
    if int(values.get("model_axis") or 1) > 1:
        raise ValueError("--loop_passes > 1 is not split over --model_axis "
                         "yet (tensor parallelism walks the layers once) — "
                         "drop one")


def _validate_pairing_flags(values: dict):
    """Parse-time loud-pairing checks promoted OUT of the dttlint
    DTT006 baseline (r18 — four entries fixed for real instead of
    suppressed): a flag that would be silently inert (or invalid) for
    the named configuration surfaces at the command line. The
    train()-time library checks that overlap these stay (non-CLI
    callers remain protected); this is the fail-fast front door, the
    --zero_overlap/--virtual_stages precedent."""
    job = values.get("job_name")
    if job is not None and job not in ("", "ps", "worker"):
        raise ValueError(
            f"--job_name={job!r} must be 'ps', 'worker' or empty "
            f"(reference semantics, MNISTDist.py:13-31: the role this "
            f"process plays in the --ps_hosts topology)")
    if values.get("sp_span_hosts") and not values.get("seq_parallel"):
        raise ValueError(
            "--sp_span_hosts only applies with --seq_parallel (it lets "
            "the TOKEN axis span processes); without it the flag would "
            "silently change nothing — drop it or add --seq_parallel")
    model = values.get("model")
    if values.get("pallas") and model is not None and \
            model != "deep_cnn":
        raise ValueError(
            f"--pallas fuses the deep_cnn FC stack's dominant matmul; "
            f"with --model={model} it would silently change nothing — "
            f"drop it or use --model=deep_cnn")
    if values.get("augment") and values.get("dataset") == "lm":
        raise ValueError(
            "--augment crops/flips images; --dataset=lm feeds token "
            "sequences with no image layout to augment — drop one")


def _validate_serving_flags(values: dict):
    """Parse-time --serve_* validation (the PR-2 _register_validator
    pattern): a non-bucketable batch size, an impossible queue bound, or
    a TP degree the head count can't divide surfaces at the command
    line, not mid-request."""
    mb = values.get("serve_max_batch")
    if mb is None:
        return  # serving flags not defined in this parse set
    mb = int(mb)
    if mb < 1:
        raise ValueError(f"--serve_max_batch={mb} must be >= 1")
    if mb & (mb - 1):
        raise ValueError(
            f"--serve_max_batch={mb} must be a power of two — batches "
            f"pad to power-of-two buckets, and a non-bucketable cap "
            f"would leave its own executable permanently cold")
    qd = int(values.get("serve_queue_depth") or 0)
    if qd < mb:
        raise ValueError(
            f"--serve_queue_depth={qd} must hold at least one full "
            f"--serve_max_batch={mb}")
    if float(values.get("serve_max_delay_ms") or 0.0) < 0:
        raise ValueError("--serve_max_delay_ms must be >= 0")
    if float(values.get("serve_timeout_ms") or 0.0) <= 0:
        raise ValueError("--serve_timeout_ms must be > 0")
    mnt = values.get("serve_max_new_tokens")
    if mnt is not None and int(mnt) < 1:
        raise ValueError("--serve_max_new_tokens must be >= 1")
    port = values.get("serve_port")
    if port is not None and not 0 <= int(port) <= 65535:
        raise ValueError(f"--serve_port={port} must be in [0, 65535] "
                         f"(0 = ephemeral)")
    temp = values.get("serve_temperature")
    if temp is not None and float(temp) < 0:
        raise ValueError("--serve_temperature must be >= 0 (0 = greedy)")
    if int(values.get("serve_profile_batches") or 0) < 0:
        raise ValueError("--serve_profile_batches must be >= 0")
    if float(values.get("serve_reload_secs") or 0.0) < 0:
        raise ValueError("--serve_reload_secs must be >= 0")
    if int(values.get("serve_metrics_every") or 0) < 0:
        raise ValueError("--serve_metrics_every must be >= 0 (0 = off)")
    tp = values.get("serve_tp")
    tp = 1 if tp is None else int(tp)
    if tp < 1:
        raise ValueError(f"--serve_tp={tp} must be >= 1")
    if tp > 1:
        heads = int(values.get("num_heads") or 0)
        if heads and heads % tp:
            raise ValueError(
                f"--serve_tp={tp} must divide --num_heads={heads} (the "
                f"attention split is head-aligned)")
        d_model = int(values.get("d_model") or 0)
        if d_model and d_model % tp:
            raise ValueError(
                f"--serve_tp={tp} must divide --d_model={d_model}")
    sched = values.get("serve_scheduler")
    if sched is not None:
        if sched not in ("whole_batch", "continuous"):
            raise ValueError(
                f"--serve_scheduler={sched!r} must be one of "
                f"whole_batch, continuous")
        slots = values.get("serve_slots")
        if slots is not None and int(slots) < 2:
            raise ValueError(
                f"--serve_slots={slots} must be >= 2 (slot width >= 2 "
                f"keeps decode on the GEMM kernel — the bitwise-parity "
                f"floor)")
        page = values.get("serve_kv_page")
        if page is not None and int(page) < 1:
            raise ValueError(f"--serve_kv_page={page} must be >= 1")
        seq_len = int(values.get("seq_len") or 0)
        if page is not None and seq_len and seq_len % int(page):
            raise ValueError(
                f"--serve_kv_page={page} must divide --seq_len="
                f"{seq_len} (a slot's pages tile the context window)")
        pages = values.get("serve_kv_pages")
        if pages is not None and int(pages) < 0:
            raise ValueError(
                f"--serve_kv_pages={pages} must be >= 0 "
                f"(0 = full provisioning)")
        if pages and page and seq_len:
            per_slot = -(-seq_len // int(page))
            if int(pages) < per_slot:
                raise ValueError(
                    f"--serve_kv_pages={pages} cannot hold one "
                    f"full-context request ({per_slot} pages of "
                    f"{page} tokens for --seq_len={seq_len})")
        if sched == "continuous":
            model = values.get("model")
            if model is not None and model != "lm":
                raise ValueError(
                    f"--serve_scheduler=continuous serves --model lm "
                    f"only (token decode); got --model={model!r}")
            if tp > 1:
                raise ValueError(
                    "--serve_scheduler=continuous serves one replica "
                    "per device; --serve_tp > 1 is whole_batch only")
    # prompt-vs-context fit is a PER-REQUEST property (prompt lengths
    # vary); decode.generate enforces it loudly at request time


def _validate_zero_flags(values: dict):
    """Parse-time --zero validation (the PR-2 _register_validator
    pattern): an unknown level, a model-axis strategy collision, or the
    async ps topology surfaces at the command line with a message that
    names the flags — not mid-trace from inside the step builder. The
    library layer re-checks (parallel/zero._check_level, loop.train) so
    non-CLI callers stay protected; this is the fail-fast front door.
    Divisibility needs NO check here: ZeRO leaves flatten and zero-pad
    to a multiple of D (parallel/zero), so every model splits over any
    data-axis size. A data axis of 1 is legal-but-pointless and depends
    on the device count, unknowable at parse time — the loop prints a
    warning at startup instead."""
    raw = values.get("zero")
    z = 0 if raw is None else int(raw)
    if z not in (0, 1, 3):
        raise ValueError(
            f"--zero={z} must be 0 (replicated DP), 1 (shard the "
            f"optimizer state over the data axis) or 3 (shard the params "
            f"too, FSDP-style); level 2 (grad persistence sharding) does "
            f"not exist in this build — grads are already transient")
    overlap = bool(values.get("zero_overlap"))
    bucket = values.get("zero_bucket_mb")
    if bucket is not None and not 0 < float(bucket) <= 1024:
        raise ValueError(
            f"--zero_bucket_mb={bucket} must be in (0, 1024] MB (one "
            f"collective per bucket; 0 or negative would bucket "
            f"nothing, >1 GB is one flat scatter by another name)")
    if overlap and z == 0:
        raise ValueError(
            "--zero_overlap only applies to --zero 1|3 (it reschedules "
            "the ZeRO collectives); without --zero it would silently "
            "change nothing — drop it or pick a --zero level")
    if not overlap and bucket is not None and float(bucket) != 4.0:
        raise ValueError(
            f"--zero_bucket_mb={bucket} only applies with "
            f"--zero_overlap (it sizes the overlap pattern's buckets); "
            f"without it the flag would silently change nothing — drop "
            f"it or add --zero_overlap")
    if z == 0:
        return
    for flag, what in (("pipeline", "pipeline stages"),
                       ("seq_parallel", "the token axis"),
                       ("expert_parallel", "MoE experts")):
        if values.get(flag):
            raise ValueError(
                f"--zero={z} with --{flag} is not supported: ZeRO "
                f"shards the whole TrainState over the DATA axis while "
                f"--{flag} shards {what} over the model axis — the two "
                f"state layouts collide. Drop one (ZeRO-over-PP/EP is a "
                f"future composition)")
    k = int(values.get("model_axis") or 1)
    if k > 1:
        raise ValueError(
            f"--zero={z} with --model_axis={k} (tensor parallelism) is "
            f"not supported: the TP GSPMD layout already partitions "
            f"params, and composing it with ZeRO's data-axis chunking "
            f"needs a 2-D sharding rule this build doesn't have. Use "
            f"--model_axis=1")
    mode = values.get("mode") or "auto"
    if mode == "ps" or values.get("ps_hosts"):
        raise ValueError(
            f"--zero={z} requires SYNCHRONOUS data parallelism (the "
            f"sharded optimizer update must see the same summed gradient "
            f"on every rank); the ps topology is asynchronous. Drop "
            f"--ps_hosts / use --mode=sync")
    if mode == "local":
        raise ValueError(
            f"--zero={z} requires sync mode (a device mesh with a data "
            f"axis to shard over); --mode=local has no mesh. Use "
            f"--mode=sync on a host with >1 device (ZeRO is "
            f"single-process in this version, so a multi-host launch "
            f"won't help) — note --mode=auto only upgrades to sync when "
            f"the host has >1 device; on a 1-chip host it resolves to "
            f"local and the run refuses at startup")


def _validate_telemetry_flags(values: dict):
    """Parse-time telemetry validation (the PR-2 _register_validator
    pattern): a negative watchdog timeout, an abort flag with no armed
    watchdog, or a zero-length flight ring surfaces at the command
    line, not as silently-dead observability mid-run."""
    wd = values.get("watchdog_s")
    wd = 0.0 if wd is None else float(wd)
    if wd < 0:
        raise ValueError(f"--watchdog_s={wd} must be >= 0 (0 = off)")
    telemetry_flag = values.get("telemetry")
    if wd > 0 and telemetry_flag is not None and not telemetry_flag:
        raise ValueError(
            "--watchdog_s > 0 with --telemetry=false is silently inert "
            "(the watchdog is part of the telemetry spine and is never "
            "installed when telemetry is off) — drop --watchdog_s or "
            "re-enable --telemetry")
    if values.get("watchdog_abort") and wd <= 0:
        raise ValueError(
            "--watchdog_abort only applies with --watchdog_s > 0 (no "
            "watchdog ever fires without a timeout); without it the "
            "flag would silently change nothing — drop it or set "
            "--watchdog_s")
    fe = values.get("flightrec_events")
    if fe is not None and int(fe) < 1:
        raise ValueError(f"--flightrec_events={fe} must be >= 1 (the "
                         f"crash postmortem needs at least one slot; "
                         f"use --telemetry=false to disable telemetry)")


def _validate_efficiency_flags(values: dict):
    """Parse-time validation of the --mfu_* / --sentinel_* surface (the
    PR-2 _register_validator pattern): an unknown sentinel kind or
    action, a sentinel armed under --telemetry=false (its spans/flight
    dumps would be silently inert), or a nonsensical window/threshold/
    peak surfaces at the command line, not mid-run."""
    if float(values.get("mfu_peak_flops") or 0.0) < 0:
        raise ValueError("--mfu_peak_flops must be >= 0 (0 = auto-detect)")
    action = (values.get("sentinel_action") or "").strip()
    if action:
        from distributed_tensorflow_tpu.utils.sentinel import (
            ACTIONS,
            parse_kinds,
        )

        if action not in ACTIONS:
            raise ValueError(
                f"--sentinel_action={action!r} must be one of "
                f"{', '.join(ACTIONS)} (or empty = unarmed)")
        telemetry_flag = values.get("telemetry")
        if telemetry_flag is not None and not telemetry_flag:
            raise ValueError(
                "--sentinel_action with --telemetry=false is silently "
                "degraded (the sentinel's trip spans and flight-recorder "
                "postmortems ride the telemetry spine) — drop "
                "--sentinel_action or re-enable --telemetry")
        try:
            parse_kinds(values.get("sentinel_kinds") or "")
        except ValueError as e:
            raise ValueError(f"--sentinel_kinds: {e}") from None
        if int(values.get("sentinel_window") or 0) < 4:
            raise ValueError(
                f"--sentinel_window={values.get('sentinel_window')} must "
                f"be >= 4 (the rolling median needs history to judge "
                f"against)")
        if float(values.get("sentinel_threshold") or 0.0) <= 0:
            raise ValueError("--sentinel_threshold must be > 0 (MADs "
                             "above the rolling median)")


def _validate_resource_flags(values: dict):
    """Parse-time validation of the resource-plane surface (the PR-2
    _register_validator pattern): out-of-bounds values, or an ARMED
    resource instrument under --telemetry=false (its samples, storm
    spans, and OOM postmortems all ride the telemetry spine and would
    be silently inert), surface at the command line with the bounds
    named — not as dead observability mid-run."""
    hse = values.get("hbm_sample_every")
    if hse is not None and int(hse) < 0:
        raise ValueError(f"--hbm_sample_every={hse} must be >= 0 "
                         f"(0 = off; N = sample every Nth display "
                         f"boundary)")
    rb = values.get("recompile_budget")
    if rb is not None and int(rb) < 0:
        raise ValueError(f"--recompile_budget={rb} must be >= 0 "
                         f"(0 = count recompiles but never trip)")
    shp = values.get("serve_hbm_headroom_pct")
    if shp is not None and not (0.0 <= float(shp) < 100.0):
        raise ValueError(f"--serve_hbm_headroom_pct={shp} must be in "
                         f"[0, 100) percent of the device limit "
                         f"(0 = off; 100 would 503 a healthy replica)")
    if shp is not None and float(shp) > 0 and hse is not None \
            and int(hse) == 0:
        raise ValueError(
            "--serve_hbm_headroom_pct > 0 with --hbm_sample_every=0 is "
            "silently inert (the drain floor reads the memory meter, "
            "which 0 disables) — drop the floor or re-enable sampling")
    telemetry_flag = values.get("telemetry")
    if telemetry_flag is None or telemetry_flag:
        return
    # telemetry off: reject explicitly-armed resource instruments (the
    # watchdog_s precedent — defaults pass, deviations in the armed
    # direction are silently inert and must be named)
    if rb is not None and int(rb) > 0:
        raise ValueError(
            "--recompile_budget > 0 with --telemetry=false is silently "
            "inert (the recompile sentry's storm spans and flight-"
            "recorder dumps ride the telemetry spine) — drop it or "
            "re-enable --telemetry")
    if shp is not None and float(shp) > 0:
        raise ValueError(
            "--serve_hbm_headroom_pct > 0 with --telemetry=false is "
            "silently inert (the serving memory meter is part of the "
            "telemetry spine and is never installed when telemetry is "
            "off) — drop it or re-enable --telemetry")
    if hse is not None and int(hse) > 1:
        raise ValueError(
            "--hbm_sample_every > 1 with --telemetry=false is silently "
            "inert (HBM sampling rides the telemetry spine; "
            "--telemetry=false already disables it) — drop one")


# the request plane's flag defaults, shared by the DEFINE_* calls and
# the telemetry=false armed-deviation checks below so they cannot
# drift (a retuned default must not start rejecting plain
# --telemetry=false invocations)
_REQTRACE_RING_DEFAULT = 512
_REQTRACE_EXEMPLARS_DEFAULT = 5


def _validate_reqtrace_flags(values: dict):
    """Parse-time validation of the request-plane surface (the PR-2
    _register_validator pattern): out-of-bounds --slo_*/--reqtrace_*
    values, an SLO target without the SLO armed, or request-plane
    knobs explicitly armed under --telemetry=false (the plane rides
    the telemetry spine and would be silently inert — the DTT006
    armed-deviation rule), all surface at the command line with the
    bounds named."""
    p99 = values.get("slo_p99_ms")
    if p99 is not None and float(p99) < 0:
        raise ValueError(f"--slo_p99_ms={p99} must be >= 0 ms "
                         f"(0 = SLO accounting off)")
    tgt = values.get("slo_target_pct")
    if tgt is not None and not (50.0 < float(tgt) <= 100.0):
        raise ValueError(f"--slo_target_pct={tgt} must be in (50, 100] "
                         f"(the promised compliant fraction; <= 50 "
                         f"leaves no meaningful error budget)")
    if tgt is not None and float(tgt) != 99.0 \
            and (p99 is None or float(p99) <= 0):
        raise ValueError(
            "--slo_target_pct without --slo_p99_ms > 0 is silently "
            "inert (the target only parameterizes the armed "
            "error-budget ledger) — set --slo_p99_ms or drop the "
            "target")
    ring = values.get("reqtrace_ring")
    if ring is not None and not (16 <= int(ring) <= 1_048_576):
        raise ValueError(f"--reqtrace_ring={ring} must be in "
                         f"[16, 1048576] retained request summaries")
    ex = values.get("reqtrace_exemplars")
    if ex is not None and not (1 <= int(ex) <= 64):
        raise ValueError(f"--reqtrace_exemplars={ex} must be in "
                         f"[1, 64] named tail exemplars")
    telemetry_flag = values.get("telemetry")
    if telemetry_flag is None or telemetry_flag:
        return
    # telemetry off: reject explicitly-armed request-plane knobs (the
    # watchdog_s precedent — defaults pass, deviations in the armed
    # direction are silently inert and must be named)
    if p99 is not None and float(p99) > 0:
        raise ValueError(
            "--slo_p99_ms > 0 with --telemetry=false is silently inert "
            "(the request plane's ledger, audit ring, and req:* spans "
            "ride the telemetry spine) — drop it or re-enable "
            "--telemetry")
    if ring is not None and int(ring) != _REQTRACE_RING_DEFAULT:
        raise ValueError(
            "--reqtrace_ring with --telemetry=false is silently inert "
            "(the audit ring is part of the request plane, which "
            "--telemetry=false leaves unconfigured) — drop it or "
            "re-enable --telemetry")
    if ex is not None and int(ex) != _REQTRACE_EXEMPLARS_DEFAULT:
        raise ValueError(
            "--reqtrace_exemplars with --telemetry=false is silently "
            "inert (the tail block is part of the request plane, which "
            "--telemetry=false leaves unconfigured) — drop it or "
            "re-enable --telemetry")


def _validate_router_flags(values: dict):
    """Parse-time validation of the fleet-router surface (r22, the
    PR-2 _register_validator pattern): --router_* bounds, a min-healthy
    floor the configured fleet cannot honor, and hedging armed under
    --telemetry=false (the hedge race is only auditable through the
    route_hedge/route_retry spans — armed-but-inert is the DTT006
    deviation rule) all surface at the command line, flags NAMED."""
    replicas = [t for t in (values.get("router_replicas") or "").split(",")
                if t.strip()]
    _require(values, "router_host", lambda v: bool(str(v).strip()),
             "must be a non-empty bind address")
    _require(values, "router_port",
             lambda v: 0 <= int(v) <= 65535,
             "must be in [0, 65535] (0 = ephemeral)")
    _require(values, "router_poll_ms",
             lambda v: 10.0 <= float(v) <= 60000.0,
             "must be in [10, 60000] ms between health sweeps")
    _require(values, "router_retries",
             lambda v: 0 <= int(v) <= 10,
             "must be in [0, 10] retry attempts")
    _require(values, "router_backoff_ms",
             lambda v: 0.0 <= float(v) <= 10000.0,
             "must be in [0, 10000] ms base backoff")
    _require(values, "router_retry_budget_pct",
             lambda v: 0.0 <= float(v) <= 100.0,
             "must be in [0, 100] percent of observed requests")
    _require(values, "router_hedge_ms",
             lambda v: 0.0 <= float(v) <= 60000.0,
             "must be in [0, 60000] ms (0 = hedging off)")
    _require(values, "router_hedge_budget_pct",
             lambda v: 0.0 <= float(v) <= 100.0,
             "must be in [0, 100] percent of observed requests")
    _require(values, "router_breaker_fails",
             lambda v: 1 <= int(v) <= 100,
             "must be in [1, 100] consecutive failures")
    _require(values, "router_eject_s",
             lambda v: 0.0 < float(v) <= 3600.0,
             "must be in (0, 3600] seconds of ejection cooldown")
    mh = values.get("router_min_healthy")
    if mh is not None and int(mh) < 0:
        raise ValueError(f"--router_min_healthy={mh} must be >= 0")
    if mh is not None and replicas and int(mh) >= len(replicas):
        raise ValueError(
            f"--router_min_healthy={mh} must be < the configured "
            f"replica count ({len(replicas)}): rolling reload drains "
            f"one replica at a time, so the floor can never be met "
            f"while any replica reloads")
    hedge = values.get("router_hedge_ms")
    telemetry_flag = values.get("telemetry")
    if (hedge is not None and float(hedge) > 0
            and telemetry_flag is not None and not telemetry_flag):
        raise ValueError(
            "--router_hedge_ms > 0 with --telemetry=false is flying "
            "blind (the hedge race books through route_hedge/"
            "route_retry spans and the request plane's SLO dedupe, "
            "all of which ride the telemetry spine) — drop the hedge "
            "or re-enable --telemetry")


def _validate_elastic_flags(values: dict):
    """Parse-time elastic-surface validation (the PR-2
    _register_validator pattern): a negative world, or elasticity armed
    on the asynchronous ps topology (whose membership is the reference's
    static ClusterSpec — there is no mesh to re-form), surfaces at the
    command line with the flags named."""
    ws = values.get("world_size")
    if ws is not None and int(ws) < 0:
        raise ValueError(f"--world_size={ws} must be >= 0 (0 = the full "
                         f"device/process set)")
    el = bool(values.get("elastic"))
    spec = values.get("fault_spec") or ""
    preempt_armed = "preempt" in spec
    if not (el or preempt_armed):
        return
    mode = values.get("mode") or "auto"
    if mode == "ps" or values.get("ps_hosts"):
        raise ValueError(
            "--elastic (or a --fault_spec preempt rule) with the ps "
            "topology is not supported: ps membership is the "
            "reference's static ClusterSpec and there is no device "
            "mesh to re-form — use --mode=sync")


def _validate_fault_spec(values: dict):
    """Parse-time --fault_spec validation: a typo'd injection point or
    mode surfaces at the command line with the registered-point list, not
    as a silently-never-firing rule mid-run."""
    spec = values.get("fault_spec") or ""
    if not spec:
        return
    from distributed_tensorflow_tpu.utils.faults import (
        FaultSpecError,
        parse_fault_spec,
    )

    try:
        parse_fault_spec(spec)
    except FaultSpecError as e:
        raise ValueError(f"--fault_spec: {e}") from None


def _validate_pipeline_flags(values: dict):
    """Parse-time pipeline-config validation: every constraint here used
    to surface as a mid-trace ValueError from inside the compiled step
    builder (parallel/pipeline_parallel._pp_step_fn) — catch it at the
    command line with a message that names the flags instead. The
    library-level checks stay (non-CLI callers are still protected);
    this is the fail-fast front door."""
    from distributed_tensorflow_tpu.parallel.pp_schedule import (
        PP_SCHEDULES,
        normalize_pp_schedule,
    )

    raw_v = values.get("virtual_stages")
    v = 1 if raw_v is None else int(raw_v)
    micro_flag = int(values.get("pp_microbatches") or 0)
    if v < 1:
        raise ValueError(f"--virtual_stages={v} must be >= 1")
    if micro_flag < 0:
        raise ValueError(f"--pp_microbatches={micro_flag} must be >= 0 "
                         f"(0 = the stage count)")
    raw_sched = (values.get("pp_schedule") or "auto").strip().lower()
    if raw_sched not in PP_SCHEDULES:
        raise ValueError(
            f"--pp_schedule={raw_sched!r} must be one of "
            f"{', '.join(PP_SCHEDULES)}")
    if not values.get("pipeline"):
        if v > 1:
            raise ValueError(
                f"--virtual_stages={v} only applies to --pipeline (the "
                f"interleaved schedule splits pipeline stages); without "
                f"--pipeline it would silently change nothing — drop it "
                f"or add --pipeline")
        if raw_sched != "auto":
            raise ValueError(
                f"--pp_schedule={raw_sched} only applies to --pipeline "
                f"(it picks the pipeline tick schedule); without "
                f"--pipeline it would silently change nothing — drop it "
                f"or add --pipeline")
        return
    # gpipe x virtual_stages>1 contradiction surfaces here with the
    # flags named; zb's V interaction is checked against the layout
    # below (same rounds rule as interleaved, plus >= 2 blocks/group)
    try:
        sched = normalize_pp_schedule(raw_sched, v)
    except ValueError as e:
        raise ValueError(f"--pp_schedule: {e}") from None
    k = int(values.get("model_axis") or 1)
    micro = micro_flag or k
    batch = int(values.get("batch_size") or 0)
    if batch and micro and batch % micro:
        raise ValueError(
            f"--batch_size={batch} must split into "
            f"--pp_microbatches={micro} microbatches (each data shard's "
            f"slice must divide further — checked against the mesh at "
            f"startup)")
    if k > 1:  # model_axis<2 is rejected with its own message at startup
        nb = int(values.get("num_blocks") or 0)
        if nb % (k * v):
            raise ValueError(
                f"--num_blocks={nb} must divide into --model_axis={k} "
                f"pipeline stages x --virtual_stages={v} block groups "
                f"({k * v} total)")
        if v > 1 and micro % k:
            raise ValueError(
                f"--virtual_stages={v} (interleaved schedule) works "
                f"microbatches in rounds of the stage count: "
                f"--pp_microbatches={micro} must be divisible by "
                f"--model_axis={k}")
        if sched == "zb" and nb and nb // (k * v) < 2:
            raise ValueError(
                f"--pp_schedule=zb needs >= 2 blocks per virtual-stage "
                f"group (the inner block scan's loop boundary is what "
                f"keeps zb bit-identical to gpipe/interleaved): "
                f"--num_blocks={nb} over --model_axis={k} x "
                f"--virtual_stages={v} leaves {nb // (k * v)} block(s) "
                f"per group — raise --num_blocks or lower the split")
