"""Model zoo: the BASELINE.json configs + the reference's embryonic zoo.

Reference: trainedmodels/TrainedModels.java (VGG16); BASELINE configs:
LeNet/MNIST MultiLayerNetwork, ResNet-50 ComputationGraph, GravesLSTM char-RNN.
All built through the public config DSL — these dual as integration tests of
the builder.
"""
from __future__ import annotations

from ..nn.conf.configuration import NeuralNetConfiguration
from ..nn.conf.inputs import InputType
from ..nn.conf.layers import (DenseLayer, OutputLayer, RnnOutputLayer,
                              ConvolutionLayer, SubsamplingLayer,
                              BatchNormalization, ActivationLayer, GravesLSTM,
                              GlobalPoolingLayer)
from ..nn.conf.graph_configuration import ElementWiseVertex, ScaleVertex
from ..nn.updaters import Adam, Nesterovs, Sgd
from ..nn.multilayer.network import MultiLayerNetwork
from ..nn.graph.graph import ComputationGraph


def lenet_mnist(seed=12345, updater=None):
    """LeNet-style CNN for MNIST (BASELINE config #1; mirrors the classic DL4J
    LeNet example built on the reference's ConvolutionLayer/SubsamplingLayer)."""
    conf = (NeuralNetConfiguration.builder()
            .seed(seed)
            .updater(updater or Nesterovs(learning_rate=0.01, momentum=0.9))
            .weight_init("xavier")
            .list()
            .layer(ConvolutionLayer(kernel_size=(5, 5), stride=(1, 1), n_out=20,
                                    activation="identity"))
            .layer(SubsamplingLayer(pooling_type="max", kernel_size=(2, 2),
                                    stride=(2, 2)))
            .layer(ConvolutionLayer(kernel_size=(5, 5), stride=(1, 1), n_out=50,
                                    activation="identity"))
            .layer(SubsamplingLayer(pooling_type="max", kernel_size=(2, 2),
                                    stride=(2, 2)))
            .layer(DenseLayer(n_out=500, activation="relu"))
            .layer(OutputLayer(n_out=10, activation="softmax", loss="MCXENT"))
            .input_type(InputType.convolutional(28, 28, 1))
            .build())
    return MultiLayerNetwork(conf)


def cifar_convnet(seed=12345, num_classes=10, updater=None):
    """Small conv net for 32x32x3 CIFAR-format data (mirrors the reference's
    Cifar example scale: two conv/pool blocks + dense head). Gated on the
    committed real-photo fixture (tests/fixtures/cifar_real) by
    tests/test_real_cifar.py."""
    conf = (NeuralNetConfiguration.builder()
            .seed(seed)
            .updater(updater or Adam(1e-3))
            .weight_init("relu")
            .list()
            .layer(ConvolutionLayer(kernel_size=(3, 3), stride=(1, 1),
                                    n_out=32, activation="relu",
                                    padding=(1, 1)))
            .layer(SubsamplingLayer(pooling_type="max", kernel_size=(2, 2),
                                    stride=(2, 2)))
            .layer(ConvolutionLayer(kernel_size=(3, 3), stride=(1, 1),
                                    n_out=64, activation="relu",
                                    padding=(1, 1)))
            .layer(SubsamplingLayer(pooling_type="max", kernel_size=(2, 2),
                                    stride=(2, 2)))
            .layer(DenseLayer(n_out=256, activation="relu"))
            .layer(OutputLayer(n_out=num_classes, activation="softmax",
                               loss="MCXENT"))
            .input_type(InputType.convolutional(32, 32, 3))
            .build())
    return MultiLayerNetwork(conf)


def mlp_mnist(seed=12345, hidden=512):
    conf = (NeuralNetConfiguration.builder()
            .seed(seed).updater(Adam(1e-3)).weight_init("relu")
            .list()
            .layer(DenseLayer(n_out=hidden, activation="relu"))
            .layer(DenseLayer(n_out=hidden // 2, activation="relu"))
            .layer(OutputLayer(n_out=10, activation="softmax", loss="MCXENT"))
            .input_type(InputType.feed_forward(784))
            .build())
    return MultiLayerNetwork(conf)


def char_rnn_lstm(vocab_size=80, hidden=256, layers=2, seed=12345, tbptt=50,
                  compute_dtype=None):
    """GravesLSTM char-RNN (BASELINE config #3). compute_dtype="bfloat16"
    runs the gemms on the MXU in bf16 while the LSTM carry and gate math
    accumulate in f32 (nn/layers/recurrent.py:_lstm_scan)."""
    from ..nn.conf.configuration import BackpropType
    b = (NeuralNetConfiguration.builder()
         .seed(seed).updater(Adam(2e-3)).weight_init("xavier")
         .compute_dtype(compute_dtype)
         .list())
    for _ in range(layers):
        b.layer(GravesLSTM(n_out=hidden, activation="tanh"))
    b.layer(RnnOutputLayer(n_out=vocab_size, activation="softmax", loss="MCXENT"))
    b.set_input_type(InputType.recurrent(vocab_size))
    b.backprop_type(BackpropType.TRUNCATED_BPTT)
    b.tbptt_fwd_length(tbptt).tbptt_back_length(tbptt)
    return MultiLayerNetwork(b.build())


def _resnet_conv_block(gb, name, n_in_name, filters, stride, bottleneck=True,
                       project=True):
    """One ResNet v1 bottleneck block: conv1x1 -> conv3x3 -> conv1x1 + skip."""
    f1, f2, f3 = filters
    gb.add_layer(f"{name}_c1", ConvolutionLayer(kernel_size=(1, 1), stride=(stride, stride),
                                                n_out=f1, activation="identity",
                                                convolution_mode="same", has_bias=False),
                 n_in_name)
    gb.add_layer(f"{name}_bn1", BatchNormalization(activation="relu"), f"{name}_c1")
    gb.add_layer(f"{name}_c2", ConvolutionLayer(kernel_size=(3, 3), stride=(1, 1),
                                                n_out=f2, activation="identity",
                                                convolution_mode="same", has_bias=False),
                 f"{name}_bn1")
    gb.add_layer(f"{name}_bn2", BatchNormalization(activation="relu"), f"{name}_c2")
    gb.add_layer(f"{name}_c3", ConvolutionLayer(kernel_size=(1, 1), stride=(1, 1),
                                                n_out=f3, activation="identity",
                                                convolution_mode="same", has_bias=False),
                 f"{name}_bn2")
    gb.add_layer(f"{name}_bn3", BatchNormalization(activation="identity"), f"{name}_c3")
    if project:
        gb.add_layer(f"{name}_proj", ConvolutionLayer(kernel_size=(1, 1),
                                                      stride=(stride, stride), n_out=f3,
                                                      activation="identity",
                                                      convolution_mode="same",
                                                      has_bias=False),
                     n_in_name)
        gb.add_layer(f"{name}_projbn", BatchNormalization(activation="identity"),
                     f"{name}_proj")
        skip = f"{name}_projbn"
    else:
        skip = n_in_name
    gb.add_vertex(f"{name}_add", ElementWiseVertex("add"), f"{name}_bn3", skip)
    gb.add_layer(f"{name}_relu", ActivationLayer(activation="relu"), f"{name}_add")
    return f"{name}_relu"


def resnet50(num_classes=1000, image_size=224, seed=12345, updater=None,
             compute_dtype=None, remat=None):
    """ResNet-50 as a ComputationGraph (BASELINE config #2). Structure follows
    the standard [3,4,6,3] bottleneck stacking; built from the same layer/vertex
    vocabulary the reference exposes (ConvolutionLayer, BatchNormalization,
    ElementWiseVertex add = residual). compute_dtype="bfloat16" enables
    TPU mixed precision (f32 params/BN stats/loss, bf16 conv+matmul);
    remat="convs_and_dots" recomputes the BN/ReLU/residual chains in the
    backward instead of storing them (nn/remat.py)."""
    gb = (NeuralNetConfiguration.builder()
          .seed(seed).updater(updater or Nesterovs(learning_rate=0.1, momentum=0.9))
          .weight_init("relu")
          .compute_dtype(compute_dtype)
          .remat(remat)
          .graph_builder()
          .add_inputs("in"))
    gb.add_layer("stem_conv", ConvolutionLayer(kernel_size=(7, 7), stride=(2, 2),
                                               n_out=64, activation="identity",
                                               convolution_mode="same", has_bias=False),
                 "in")
    gb.add_layer("stem_bn", BatchNormalization(activation="relu"), "stem_conv")
    gb.add_layer("stem_pool", SubsamplingLayer(pooling_type="max", kernel_size=(3, 3),
                                               stride=(2, 2), convolution_mode="same"),
                 "stem_bn")
    prev = "stem_pool"
    stages = [
        ("s2", (64, 64, 256), 3, 1),
        ("s3", (128, 128, 512), 4, 2),
        ("s4", (256, 256, 1024), 6, 2),
        ("s5", (512, 512, 2048), 3, 2),
    ]
    for sname, filters, blocks, stride in stages:
        prev = _resnet_conv_block(gb, f"{sname}b1", prev, filters, stride, project=True)
        for i in range(1, blocks):
            prev = _resnet_conv_block(gb, f"{sname}b{i+1}", prev, filters, 1,
                                      project=False)
    gb.add_layer("avgpool", GlobalPoolingLayer(pooling_type="avg"), prev)
    gb.add_layer("out", OutputLayer(n_out=num_classes, activation="softmax",
                                    loss="MCXENT"), "avgpool")
    gb.set_outputs("out")
    gb.set_input_types(InputType.convolutional(image_size, image_size, 3))
    return ComputationGraph(gb.build())


def transformer_lm(vocab_size=256, d_model=256, n_layers=4, n_heads=4,
                   ffn_mult=4, seed=12345, causal=True, use_pallas=False,
                   compute_dtype=None, updater=None, remat=None):
    """Decoder-only transformer language model — NEW model family beyond the
    reference's 2017 zoo (no attention exists in DL4J v0.7.3; SURVEY.md §5
    names long-context attention as this framework's new capability). Built
    from the same DSL vocabulary as everything else: SelfAttentionLayer
    (optionally the Pallas flash kernel), LayerNormalization (post-norm),
    per-timestep Dense FFN, ElementWiseVertex residuals. Input: one-hot
    [b, t, vocab]; output: next-token softmax per position.
    remat="dots" is the long-context memory dial: saved activations scale
    with n_layers*T*d_model, and recomputing the LN/residual/softmax chains
    in the backward trades idle MXU time for that memory (nn/remat.py)."""
    from ..nn.conf.layers import LayerNormalization, SelfAttentionLayer
    gb = (NeuralNetConfiguration.builder()
          .seed(seed).updater(updater or Adam(3e-4)).weight_init("xavier")
          .compute_dtype(compute_dtype)
          .remat(remat)
          .graph_builder()
          .add_inputs("tokens"))
    gb.add_layer("embed", DenseLayer(n_out=d_model, activation="identity"),
                 "tokens")
    prev = "embed"
    for i in range(n_layers):
        gb.add_layer(f"b{i}_attn",
                     SelfAttentionLayer(n_out=d_model, n_heads=n_heads,
                                        causal=causal, use_pallas=use_pallas,
                                        activation="identity"), prev)
        gb.add_vertex(f"b{i}_res1", ElementWiseVertex("add"), prev, f"b{i}_attn")
        gb.add_layer(f"b{i}_ln1", LayerNormalization(), f"b{i}_res1")
        gb.add_layer(f"b{i}_ffn1", DenseLayer(n_out=d_model * ffn_mult,
                                              activation="relu"), f"b{i}_ln1")
        gb.add_layer(f"b{i}_ffn2", DenseLayer(n_out=d_model,
                                              activation="identity"),
                     f"b{i}_ffn1")
        gb.add_vertex(f"b{i}_res2", ElementWiseVertex("add"), f"b{i}_ln1",
                      f"b{i}_ffn2")
        gb.add_layer(f"b{i}_ln2", LayerNormalization(), f"b{i}_res2")
        prev = f"b{i}_ln2"
    gb.add_layer("out", RnnOutputLayer(n_out=vocab_size, activation="softmax",
                                       loss="MCXENT"), prev)
    gb.set_outputs("out")
    gb.set_input_types(InputType.recurrent(vocab_size))
    return ComputationGraph(gb.build())


def granite_hybrid_lm(vocab_size=256, d_model=64, n_layers=10, n_heads=4,
                      ffn_mult=4, n_kv_heads=None, attention_layers=(5,),
                      mamba_n_heads=None, mamba_d_head=16, mamba_d_state=16,
                      mamba_d_conv=4, mamba_chunk_size=256,
                      embedding_multiplier=1.0, attention_multiplier=None,
                      residual_multiplier=1.0, logits_scaling=1.0,
                      rms_norm_eps=1e-5, dtype="float32", seed=12345,
                      use_pallas=False, updater=None, n_experts=0,
                      experts_per_token=0, expert_hidden=None,
                      experts_held=None, first_expert=0):
    """Hybrid state-space / attention decoder of the `granitemoehybrid`
    shape (ibm-granite/granite-4.0-h-*): pre-norm blocks h += r *
    mixer(RMSNorm(h)); h += r * ffn(RMSNorm(h)), the mixer a Mamba2Layer
    except at `attention_layers` (0-based), where it is causal grouped-query
    attention without positional encoding, scores times
    `attention_multiplier`; the ffn a gated SiLU feed-forward of width
    int(d_model * ffn_mult) and, with `n_experts` > 0, beside it on the same
    norm and added to it, `experts_per_token` of `n_experts` routed gated
    experts of width `expert_hidden`, of which this model holds
    `experts_held` from `first_expert` on (default: all; the rest of the sum
    is another chip's); h_0 = embedding_multiplier * E[ids]; probabilities =
    softmax(RMSNorm(h) E^T / logits_scaling). Input one-hot [b, t, vocab].

    `dtype` is the parameters' and activations' dtype (the decode engine
    decodes in it). The head is tied by value, not by leaf: `out/W` has the
    embedding's [vocab, d_model] layout, so whoever places weights gives
    both leaves ONE buffer; init() draws them apart. The default updater is
    plain SGD: it keeps no state beside the parameters."""
    from ..nn.conf.layers import (GatedDenseLayer, LMHeadLayer, Mamba2Layer,
                                  MixtureOfExpertsLayer, RMSNormalization,
                                  SelfAttentionLayer)
    if mamba_n_heads is None:
        mamba_n_heads = 2 * d_model // mamba_d_head
    gb = (NeuralNetConfiguration.builder()
          .seed(seed).updater(updater or Sgd(learning_rate=1e-3))
          .weight_init("xavier").dtype(dtype)
          .graph_builder()
          .add_inputs("tokens"))
    norm = lambda: RMSNormalization(eps=rms_norm_eps)

    def residual(name, prev, branch):
        gb.add_vertex(name + "x", ScaleVertex(residual_multiplier), branch)
        gb.add_vertex(name, ElementWiseVertex("add"), prev, name + "x")
        return name

    gb.add_layer("embed", DenseLayer(n_out=d_model, activation="identity"),
                 "tokens")
    gb.add_vertex("embed_x", ScaleVertex(embedding_multiplier), "embed")
    prev = "embed_x"
    for i in range(n_layers):
        gb.add_layer(f"b{i}_norm1", norm(), prev)
        if i in attention_layers:
            mixer = f"b{i}_attn"
            gb.add_layer(mixer, SelfAttentionLayer(
                n_out=d_model, n_heads=n_heads, n_kv_heads=n_kv_heads,
                score_scale=attention_multiplier, causal=True,
                use_pallas=use_pallas, activation="identity"), f"b{i}_norm1")
        else:
            mixer = f"b{i}_mamba"
            gb.add_layer(mixer, Mamba2Layer(
                n_out=d_model, n_heads=mamba_n_heads, head_dim=mamba_d_head,
                d_state=mamba_d_state, d_conv=mamba_d_conv,
                chunk_size=mamba_chunk_size, eps=rms_norm_eps,
                use_pallas=use_pallas), f"b{i}_norm1")
        prev = residual(f"b{i}_res1", prev, mixer)
        gb.add_layer(f"b{i}_norm2", norm(), prev)
        ffn = f"b{i}_mlp"
        gb.add_layer(ffn, GatedDenseLayer(
            n_out=d_model, n_hidden=int(d_model * ffn_mult)), f"b{i}_norm2")
        if n_experts:
            gb.add_layer(f"b{i}_moe", MixtureOfExpertsLayer(
                n_out=d_model, n_experts=n_experts, top_k=experts_per_token,
                gated=True, n_hidden=expert_hidden, experts_held=experts_held,
                first_expert=first_expert, use_pallas=use_pallas,
                activation="identity"), f"b{i}_norm2")
            ffn = f"b{i}_ffn"
            gb.add_vertex(ffn, ElementWiseVertex("add"), f"b{i}_moe",
                          f"b{i}_mlp")
        prev = residual(f"b{i}_res2", prev, ffn)
    gb.add_layer("norm", norm(), prev)
    gb.add_layer("out", LMHeadLayer(n_out=vocab_size, activation="softmax",
                                    loss="MCXENT",
                                    logits_scaling=logits_scaling), "norm")
    gb.set_outputs("out")
    gb.set_input_types(InputType.recurrent(vocab_size))
    return ComputationGraph(gb.build())


def ling_hybrid_lm(vocab_size=256, d_model=160, n_layers=6, n_heads=2,
                   ffn_mult=2.4, layer_group_size=6, first_k_dense=2,
                   kda_head_dim=128, kda_d_conv=4, kda_chunk_size=64,
                   kda_lower_bound=-5.0, kv_lora_rank=512,
                   qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                   rope_theta=6000000.0, n_experts=512, n_groups=8,
                   topk_groups=4, experts_per_token=8, routed_scaling=2.5,
                   expert_hidden=768, shared_hidden=768, experts_held=None,
                   first_expert=0, expert_swiglu_limits=(),
                   shared_swiglu_limits=(), rms_norm_eps=1e-6,
                   dtype="float32", seed=12345, use_pallas=False,
                   updater=None):
    """Hybrid linear-attention / latent-attention expert decoder of the
    `bailing_hybrid` shape (inclusionAI/Ling-3.0-flash): pre-norm blocks h +=
    mixer(RMSNorm(h)); h += ffn(RMSNorm(h)). Layer i (0-based) mixes with a
    LatentAttentionLayer when (i + 1) % layer_group_size == 0, else with a
    KimiDeltaAttentionLayer; its ffn is a gated SiLU feed-forward of width
    int(d_model * ffn_mult) for i < first_k_dense, else `experts_per_token`
    of `n_experts` routed gated experts of width `expert_hidden` — sigmoid
    scores, a selection-only bias, the `topk_groups` best of `n_groups`
    groups, gates renormalised and times `routed_scaling` — beside a shared
    expert of width `shared_hidden` on the same norm; this model holds
    `experts_held` of the routed experts from `first_expert` on (default:
    all; the rest of the sum is another chip's). h_0 = E[ids]; probabilities
    = softmax(RMSNorm(h) W_head^T), the head untied. Input one-hot [b, t,
    vocab].

    The published clamp on an expert's gated product
    (`expert_swiglu_limit_list`, `share_expert_swiglu_limit_list`) is NOT
    implemented: a non-zero limit among those given is refused here rather
    than guessed at. The default updater is plain SGD: it keeps no state
    beside the parameters."""
    from ..nn.conf.layers import (GatedDenseLayer, KimiDeltaAttentionLayer,
                                  LatentAttentionLayer, LMHeadLayer,
                                  MixtureOfExpertsLayer, RMSNormalization)
    if any(expert_swiglu_limits) or any(shared_swiglu_limits):
        raise ValueError(
            "a non-zero swiglu limit is not implemented: the source gives "
            "the limit and not the clamp's form (expert_swiglu_limits "
            f"{list(expert_swiglu_limits)}, shared_swiglu_limits "
            f"{list(shared_swiglu_limits)})")
    gb = (NeuralNetConfiguration.builder()
          .seed(seed).updater(updater or Sgd(learning_rate=1e-3))
          .weight_init("xavier").dtype(dtype)
          .graph_builder()
          .add_inputs("tokens"))
    norm = lambda: RMSNormalization(eps=rms_norm_eps)

    def residual(name, prev, branch):
        gb.add_vertex(name, ElementWiseVertex("add"), prev, branch)
        return name

    gb.add_layer("embed", DenseLayer(n_out=d_model, activation="identity"),
                 "tokens")
    prev = "embed"
    for i in range(n_layers):
        gb.add_layer(f"b{i}_norm1", norm(), prev)
        if (i + 1) % layer_group_size == 0:
            mixer = f"b{i}_mla"
            gb.add_layer(mixer, LatentAttentionLayer(
                n_out=d_model, n_heads=n_heads, kv_lora_rank=kv_lora_rank,
                qk_nope_head_dim=qk_nope_head_dim,
                qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
                rope_theta=rope_theta, eps=rms_norm_eps,
                use_pallas=use_pallas), f"b{i}_norm1")
        else:
            mixer = f"b{i}_kda"
            gb.add_layer(mixer, KimiDeltaAttentionLayer(
                n_out=d_model, n_heads=n_heads, head_dim=kda_head_dim,
                d_conv=kda_d_conv, chunk_size=kda_chunk_size,
                gate_lower_bound=kda_lower_bound, eps=rms_norm_eps,
                use_pallas=use_pallas), f"b{i}_norm1")
        prev = residual(f"b{i}_res1", prev, mixer)
        gb.add_layer(f"b{i}_norm2", norm(), prev)
        dense = i < first_k_dense
        ffn = f"b{i}_mlp"
        gb.add_layer(ffn, GatedDenseLayer(
            n_out=d_model, n_hidden=int(d_model * ffn_mult) if dense
            else shared_hidden), f"b{i}_norm2")
        if not dense:
            gb.add_layer(f"b{i}_moe", MixtureOfExpertsLayer(
                n_out=d_model, n_experts=n_experts, top_k=experts_per_token,
                gated=True, n_hidden=expert_hidden, experts_held=experts_held,
                first_expert=first_expert, score_function="sigmoid",
                n_groups=n_groups, topk_groups=topk_groups,
                routed_scaling=routed_scaling, use_pallas=use_pallas,
                activation="identity"), f"b{i}_norm2")
            ffn = f"b{i}_ffn"
            gb.add_vertex(ffn, ElementWiseVertex("add"), f"b{i}_moe",
                          f"b{i}_mlp")
        prev = residual(f"b{i}_res2", prev, ffn)
    gb.add_layer("norm", norm(), prev)
    gb.add_layer("out", LMHeadLayer(n_out=vocab_size, activation="softmax",
                                    loss="MCXENT"), "norm")
    gb.set_outputs("out")
    gb.set_input_types(InputType.recurrent(vocab_size))
    return ComputationGraph(gb.build())


def solar_hybrid_lm(vocab_size=256, d_model=128, n_layers=4, n_heads=2,
                    n_kv_heads=1, head_dim=128, gqa_interval=3, ffn_mult=2.5,
                    kda_head_dim=128, kda_d_conv=4, kda_chunk_size=64,
                    kda_gate_rank=128, n_experts=320, experts_per_token=8,
                    routed_scaling=1.0, expert_hidden=1280,
                    shared_hidden=1280, experts_held=None, first_expert=0,
                    rms_norm_eps=1e-5, dtype="float32", seed=12345,
                    use_pallas=False, updater=None):
    """Hybrid attention / linear-attention expert decoder of the
    `solar_open2` shape (upstage/Solar-Open2-250B): pre-norm blocks h +=
    mixer(RMSNorm(h)); h += ffn(RMSNorm(h)). Layer i (0-based) mixes with
    gated grouped-query attention — `n_heads` query heads on `n_kv_heads`
    K/V heads of `head_dim`, no positions of any kind, the context times
    sigmoid(x Wgate) before Wo — when i % (gqa_interval + 1) == 0 (the
    period STARTS with it), else with a KimiDeltaAttentionLayer of `n_heads`
    heads in Kimi Linear's own form: the unbounded softplus decay, decay and
    output gates of rank `kda_gate_rank`, beta in (0, 2). Its ffn, in every
    layer, is `experts_per_token` of `n_experts` routed gated experts of
    width `expert_hidden` — sigmoid scores, a selection-only bias, one
    group, gates renormalised and times `routed_scaling` — beside a shared
    expert of width `shared_hidden` on the same norm; this model holds
    `experts_held` of the routed experts from `first_expert` on (default:
    all; the rest of the sum is another chip's). `ffn_mult` builds nothing:
    it is the published `intermediate_size` of a dense layer the model does
    not have, accepted because a configuration file's `args` carry it for
    the benchmark's `model_dims`. h_0 = E[ids]; probabilities =
    softmax(RMSNorm(h) W_head^T), the head untied. Input one-hot [b, t,
    vocab]. The default updater is plain SGD: it keeps no state beside the
    parameters."""
    from ..nn.conf.layers import (GatedDenseLayer, KimiDeltaAttentionLayer,
                                  LMHeadLayer, MixtureOfExpertsLayer,
                                  RMSNormalization, SelfAttentionLayer)
    gb = (NeuralNetConfiguration.builder()
          .seed(seed).updater(updater or Sgd(learning_rate=1e-3))
          .weight_init("xavier").dtype(dtype)
          .graph_builder()
          .add_inputs("tokens"))
    norm = lambda: RMSNormalization(eps=rms_norm_eps)

    def residual(name, prev, branch):
        gb.add_vertex(name, ElementWiseVertex("add"), prev, branch)
        return name

    gb.add_layer("embed", DenseLayer(n_out=d_model, activation="identity"),
                 "tokens")
    prev = "embed"
    for i in range(n_layers):
        gb.add_layer(f"b{i}_norm1", norm(), prev)
        if i % (gqa_interval + 1) == 0:
            mixer = f"b{i}_attn"
            gb.add_layer(mixer, SelfAttentionLayer(
                n_out=d_model, n_heads=n_heads, n_kv_heads=n_kv_heads,
                head_dim=head_dim, output_gate=True, causal=True,
                use_pallas=use_pallas, activation="identity"), f"b{i}_norm1")
        else:
            mixer = f"b{i}_kda"
            gb.add_layer(mixer, KimiDeltaAttentionLayer(
                n_out=d_model, n_heads=n_heads, head_dim=kda_head_dim,
                d_conv=kda_d_conv, chunk_size=kda_chunk_size,
                gate_form="softplus", gate_rank=kda_gate_rank, beta_scale=2.0,
                eps=rms_norm_eps, use_pallas=use_pallas), f"b{i}_norm1")
        prev = residual(f"b{i}_res1", prev, mixer)
        gb.add_layer(f"b{i}_norm2", norm(), prev)
        gb.add_layer(f"b{i}_mlp", GatedDenseLayer(
            n_out=d_model, n_hidden=shared_hidden), f"b{i}_norm2")
        gb.add_layer(f"b{i}_moe", MixtureOfExpertsLayer(
            n_out=d_model, n_experts=n_experts, top_k=experts_per_token,
            gated=True, n_hidden=expert_hidden, experts_held=experts_held,
            first_expert=first_expert, score_function="sigmoid",
            n_groups=1, routed_scaling=routed_scaling,
            use_pallas=use_pallas, activation="identity"), f"b{i}_norm2")
        gb.add_vertex(f"b{i}_ffn", ElementWiseVertex("add"), f"b{i}_moe",
                      f"b{i}_mlp")
        prev = residual(f"b{i}_res2", prev, f"b{i}_ffn")
    gb.add_layer("norm", norm(), prev)
    gb.add_layer("out", LMHeadLayer(n_out=vocab_size, activation="softmax",
                                    loss="MCXENT"), "norm")
    gb.set_outputs("out")
    gb.set_input_types(InputType.recurrent(vocab_size))
    return ComputationGraph(gb.build())


def mellum_lm(vocab_size=256, d_model=128, n_layers=4, n_heads=4,
              n_kv_heads=1, head_dim=128, window=1024, full_interval=3,
              rope_theta=500000.0, yarn=None, n_experts=64,
              experts_per_token=8, expert_hidden=896, experts_held=None,
              first_expert=0, rms_norm_eps=1e-6, dtype="float32", seed=12345,
              use_pallas=False, updater=None):
    """Sliding-window / full-attention expert decoder of the `mellum` shape
    (JetBrains/Mellum2-12B-A2.5B-Instruct): pre-norm blocks h +=
    attention(RMSNorm(h)); h += experts(RMSNorm(h)). Every layer mixes with
    grouped-query attention — `n_heads` query heads on `n_kv_heads` K/V
    heads of `head_dim`, rotary positions on q and k, no bias, no q/k norm.
    Layer i (0-based) is a FULL layer when i % (full_interval + 1) ==
    full_interval (the period ends with it): the whole context, its rotary
    frequencies YaRN's (`yarn`: {"factor", "original_max_position_embeddings",
    "beta_fast", "beta_slow", "attention_factor"}; None: plain); every other
    layer sees a sliding `window` of positions, its own among them, under
    plain rotary at `rope_theta`, and decodes from a ring of `window`
    positions a slot. Its ffn, in every layer, is `experts_per_token` of
    `n_experts` routed gated-SiLU experts of width `expert_hidden` — softmax
    over all experts, the largest taken, their gates renormalised — with no
    shared expert beside them; this model holds `experts_held` of the
    experts from `first_expert` on (default: all; the rest of the sum is
    another chip's). h_0 = E[ids]; probabilities = softmax(RMSNorm(h)
    W_head^T), the head untied. Input one-hot [b, t, vocab]. The default
    updater is plain SGD: it keeps no state beside the parameters."""
    from ..nn.conf.layers import (LMHeadLayer, MixtureOfExpertsLayer,
                                  RMSNormalization, SelfAttentionLayer)
    gb = (NeuralNetConfiguration.builder()
          .seed(seed).updater(updater or Sgd(learning_rate=1e-3))
          .weight_init("xavier").dtype(dtype)
          .graph_builder()
          .add_inputs("tokens"))
    norm = lambda: RMSNormalization(eps=rms_norm_eps)

    def residual(name, prev, branch):
        gb.add_vertex(name, ElementWiseVertex("add"), prev, branch)
        return name

    gb.add_layer("embed", DenseLayer(n_out=d_model, activation="identity"),
                 "tokens")
    prev = "embed"
    for i in range(n_layers):
        full = i % (full_interval + 1) == full_interval
        gb.add_layer(f"b{i}_norm1", norm(), prev)
        gb.add_layer(f"b{i}_attn", SelfAttentionLayer(
            n_out=d_model, n_heads=n_heads, n_kv_heads=n_kv_heads,
            head_dim=head_dim, causal=True, rope_theta=rope_theta,
            rope_yarn=dict(yarn) if full and yarn else None,
            window=None if full else window, use_pallas=use_pallas,
            activation="identity"), f"b{i}_norm1")
        prev = residual(f"b{i}_res1", prev, f"b{i}_attn")
        gb.add_layer(f"b{i}_norm2", norm(), prev)
        gb.add_layer(f"b{i}_moe", MixtureOfExpertsLayer(
            n_out=d_model, n_experts=n_experts, top_k=experts_per_token,
            gated=True, n_hidden=expert_hidden, experts_held=experts_held,
            first_expert=first_expert, score_function="softmax",
            use_pallas=use_pallas, activation="identity"), f"b{i}_norm2")
        prev = residual(f"b{i}_res2", prev, f"b{i}_moe")
    gb.add_layer("norm", norm(), prev)
    gb.add_layer("out", LMHeadLayer(n_out=vocab_size, activation="softmax",
                                    loss="MCXENT"), "norm")
    gb.set_outputs("out")
    gb.set_input_types(InputType.recurrent(vocab_size))
    return ComputationGraph(gb.build())


def kimi_k2_lm(vocab_size=256, d_model=128, n_layers=5, n_heads=2,
               ffn_mult=18432 / 7168, first_k_dense=1, q_lora_rank=1536,
               kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
               v_head_dim=128, rope_theta=50000.0, yarn=None, n_experts=384,
               experts_per_token=8, routed_scaling=2.827, expert_hidden=2048,
               shared_hidden=2048, experts_held=None, first_expert=0,
               rms_norm_eps=1e-5, dtype="float32", seed=12345,
               use_pallas=False, updater=None):
    """Latent-attention expert decoder of the `kimi_k2` shape
    (moonshotai/Kimi-K2.7-Code; DeepSeek-V3's block): pre-norm blocks h +=
    attention(RMSNorm(h)); h += ffn(RMSNorm(h)). EVERY layer mixes with a
    LatentAttentionLayer — `n_heads` heads, queries compressed through a
    normed latent of `q_lora_rank`, keys and values made from one normed
    latent of `kv_lora_rank` beside one shared rotary key, rotary
    frequencies YaRN's (`yarn`: {"factor", "original_max_position_embeddings",
    "beta_fast", "beta_slow", "mscale", "mscale_all_dim"}; None: plain), no
    output gate. Layer i (0-based) has a gated SiLU feed-forward of width
    round(d_model * ffn_mult) for i < first_k_dense, else
    `experts_per_token` of `n_experts` routed gated experts of width
    `expert_hidden` — sigmoid scores, a selection-only bias, one group,
    gates renormalised and times `routed_scaling` — beside a shared expert
    of width `shared_hidden` on the same norm; this model holds
    `experts_held` of the routed experts from `first_expert` on (default:
    all; the rest of the sum is another chip's). h_0 = E[ids]; probabilities
    = softmax(RMSNorm(h) W_head^T), the head untied. Input one-hot [b, t,
    vocab]. The default updater is plain SGD: it keeps no state beside the
    parameters."""
    from ..nn.conf.layers import (GatedDenseLayer, LatentAttentionLayer,
                                  LMHeadLayer, MixtureOfExpertsLayer,
                                  RMSNormalization)
    gb = (NeuralNetConfiguration.builder()
          .seed(seed).updater(updater or Sgd(learning_rate=1e-3))
          .weight_init("xavier").dtype(dtype)
          .graph_builder()
          .add_inputs("tokens"))
    norm = lambda: RMSNormalization(eps=rms_norm_eps)

    def residual(name, prev, branch):
        gb.add_vertex(name, ElementWiseVertex("add"), prev, branch)
        return name

    gb.add_layer("embed", DenseLayer(n_out=d_model, activation="identity"),
                 "tokens")
    prev = "embed"
    for i in range(n_layers):
        gb.add_layer(f"b{i}_norm1", norm(), prev)
        gb.add_layer(f"b{i}_mla", LatentAttentionLayer(
            n_out=d_model, n_heads=n_heads, q_lora_rank=q_lora_rank,
            kv_lora_rank=kv_lora_rank, qk_nope_head_dim=qk_nope_head_dim,
            qk_rope_head_dim=qk_rope_head_dim, v_head_dim=v_head_dim,
            rope_theta=rope_theta, rope_yarn=dict(yarn) if yarn else None,
            output_gate=False, eps=rms_norm_eps, use_pallas=use_pallas),
            f"b{i}_norm1")
        prev = residual(f"b{i}_res1", prev, f"b{i}_mla")
        gb.add_layer(f"b{i}_norm2", norm(), prev)
        dense = i < first_k_dense
        ffn = f"b{i}_mlp"
        gb.add_layer(ffn, GatedDenseLayer(
            n_out=d_model, n_hidden=int(round(d_model * ffn_mult)) if dense
            else shared_hidden), f"b{i}_norm2")
        if not dense:
            gb.add_layer(f"b{i}_moe", MixtureOfExpertsLayer(
                n_out=d_model, n_experts=n_experts, top_k=experts_per_token,
                gated=True, n_hidden=expert_hidden, experts_held=experts_held,
                first_expert=first_expert, score_function="sigmoid",
                n_groups=1, routed_scaling=routed_scaling,
                use_pallas=use_pallas, activation="identity"), f"b{i}_norm2")
            ffn = f"b{i}_ffn"
            gb.add_vertex(ffn, ElementWiseVertex("add"), f"b{i}_moe",
                          f"b{i}_mlp")
        prev = residual(f"b{i}_res2", prev, ffn)
    gb.add_layer("norm", norm(), prev)
    gb.add_layer("out", LMHeadLayer(n_out=vocab_size, activation="softmax",
                                    loss="MCXENT"), "norm")
    gb.set_outputs("out")
    gb.set_input_types(InputType.recurrent(vocab_size))
    return ComputationGraph(gb.build())


def vgg16(num_classes=1000, image_size=224, seed=12345):
    """VGG16 (reference: trainedmodels/TrainedModels.java VGG16)."""
    b = (NeuralNetConfiguration.builder()
         .seed(seed).updater(Nesterovs(learning_rate=0.01, momentum=0.9))
         .weight_init("relu")
         .list())
    cfg = [64, 64, "M", 128, 128, "M", 256, 256, 256, "M", 512, 512, 512, "M",
           512, 512, 512, "M"]
    for v in cfg:
        if v == "M":
            b.layer(SubsamplingLayer(pooling_type="max", kernel_size=(2, 2),
                                     stride=(2, 2)))
        else:
            b.layer(ConvolutionLayer(kernel_size=(3, 3), stride=(1, 1), n_out=v,
                                     activation="relu", convolution_mode="same"))
    b.layer(DenseLayer(n_out=4096, activation="relu"))
    b.layer(DenseLayer(n_out=4096, activation="relu"))
    b.layer(OutputLayer(n_out=num_classes, activation="softmax", loss="MCXENT"))
    b.set_input_type(InputType.convolutional(image_size, image_size, 3))
    return MultiLayerNetwork(b.build())
