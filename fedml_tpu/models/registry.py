"""Model registry: model-name × dataset → ModelDef
(ref fedml_experiments/base.py:103-140 create_model dispatch; MODELS tuple at
base.py:18-26)."""

from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp

from fedml_tpu.models import ModelDef


def create(
    model_name: str,
    dataset_name: str,
    input_shape: Tuple[int, ...],
    num_classes: int,
    pretrained: str | None = None,
    **kw,
) -> ModelDef:
    name = model_name.lower()
    ds = (dataset_name or "").lower()
    if pretrained is not None:
        # ref resnet56(pretrained=True, path=...) (resnet.py:200-222):
        # build the model, then pour the checkpoint over init at first use.
        model = create(model_name, dataset_name, input_shape, num_classes, **kw)
        return _with_pretrained(model, pretrained)

    if name == "lr":
        from fedml_tpu.models.linear import LogisticRegression

        return ModelDef(
            LogisticRegression(num_classes=num_classes),
            input_shape, num_classes, name="lr",
        )

    if name == "cnn":
        # ref base.py:110-111 builds CNNDropOut for femnist under the name
        # "cnn"; we expose the original-FedAvg CNN as "cnn" and the dropout
        # variant as "cnn_dropout" (both in the reference's model zoo).
        from fedml_tpu.models.cnn import CNNOriginalFedAvg

        return ModelDef(
            CNNOriginalFedAvg(num_classes=num_classes),
            input_shape, num_classes, name="cnn",
        )

    if name == "cnn_dropout":
        from fedml_tpu.models.cnn import CNNDropOut

        return ModelDef(
            CNNDropOut(num_classes=num_classes),
            input_shape, num_classes, has_dropout=True, name="cnn_dropout",
        )

    if name == "rnn":
        # dataset selects the variant (ref base.py:108-120).
        if ds in ("stackoverflow_nwp", "stackoverflow"):
            from fedml_tpu.models.rnn import RNNStackOverFlow

            m = RNNStackOverFlow(**kw)
            ext = m.vocab_size + 3 + m.num_oov_buckets
            return ModelDef(
                m, input_shape, ext, input_dtype=jnp.int32, name="rnn_stackoverflow",
            )
        from fedml_tpu.models.rnn import RNNOriginalFedAvg

        seq_output = ds == "fed_shakespeare"
        m = RNNOriginalFedAvg(seq_output=seq_output, **kw)
        return ModelDef(
            m, input_shape, m.vocab_size, input_dtype=jnp.int32, name="rnn",
        )

    if name == "transformer":
        # Federated causal-LM fine-tuning — the FedNLP leg (the reference
        # only carries a pointer README, applications/FedNLP/README.md; its
        # in-repo NLP ceiling is the 2-layer LSTM). num_classes = vocab
        # size; trains under task="nwp" like the RNNs, so every federated
        # algorithm (FedAvg/FedOpt/FedProx/...) runs it unchanged.
        from fedml_tpu.models.transformer import TransformerLM, causal_attention

        if kw.get("moe_experts"):
            raise ValueError(
                "TransformerLM(moe_experts=...) returns (logits, aux) and "
                "trains through parallel/expert_parallel.py; on the federated "
                "ModelDef path routed experts are the 'decoder' model "
                "(models/decoder.py: num_experts, num_experts_per_tok, "
                "experts_held)"
            )
        kw.setdefault("max_len", int(input_shape[0]))
        m = TransformerLM(vocab_size=num_classes, **kw)
        site = (m.num_heads, m.num_heads, m.embed_dim // m.num_heads)
        return ModelDef(
            m, input_shape, num_classes, input_dtype=jnp.int32,
            name="transformer",
            attention_sites=(site,) * m.num_layers if m.attn_fn is causal_attention else (),
        )

    if name == "decoder":
        # Spec-driven decoder (grouped-query or latent attention, rotary/YaRN,
        # window and full layers, layers of a gated short convolution, leading
        # dense layers, routed experts with a shared one beside them; or a
        # stack of one part a layer: Mamba-2 state-space mixers, attention
        # without positions, ungated experts, dense MLPs); kw
        # mirrors the source model's config.json
        # keys, lists and nested mappings included (flax freezes them as
        # given). Returns logits only and trains under task="nwp" like
        # "transformer"; its expert layers' counters ride with the round's
        # metrics (ModelDef.counters).
        from fedml_tpu.models.decoder import DecoderLM, ExpertSpec, counter_names

        m = DecoderLM(vocab_size=num_classes, **kw)
        # a spec that cannot be expressed fails here, by name, not at first trace
        routed = [f for f in m.feed_forwards() if isinstance(f, ExpertSpec)]
        attrs = {}
        if routed:
            # ``layers`` is what the experts' counters are summed over: the
            # expert layers (every layer, where none is dense)
            attrs = {"hidden": m.hidden_size, "expert_width": routed[0].width,
                     "layers": len(routed), "expert_layers": len(routed),
                     "top_k": routed[0].top_k,
                     # grouped products a held pair runs forward: 3 gated, 2 ungated
                     "expert_products": routed[0].products()}
            if routed[0].shared_width:
                attrs["shared_width"] = routed[0].shared_width
        if m.conv_taps():
            attrs.update(conv_layers=m.kinds().count("conv"), conv_width=m.hidden_size)
        # attention layers of more than one shape: the per-kind numbers
        attrs.update(m.attention_constants(input_shape[0]))
        ssm = m.mamba_spec()
        if ssm is not None:
            attrs.update(ssm_layers=m.kinds().count("mamba"), ssm_heads=ssm.heads,
                         ssm_head_dim=ssm.head_dim, ssm_state=ssm.state,
                         ssm_groups=ssm.groups, ssm_chunk=ssm.chunk)
        return ModelDef(
            m, input_shape, num_classes, input_dtype=jnp.int32, name="decoder",
            counters=counter_names(routed[0].biased) if routed else (),
            counter_attrs=attrs,
            attention_sites=m.attention_sites(),
            rope_sites=m.rope_sites(),
            grouped_sites=m.grouped_sites if routed else None,
        )

    if name in ("resnet56", "resnet110"):
        from fedml_tpu.models import resnet

        m = getattr(resnet, name)(num_classes)
        return ModelDef(
            m, input_shape, num_classes, has_batch_stats=True, name=name,
        )

    if name in ("resnet18_gn", "resnet34_gn", "resnet50_gn", "resnet101_gn", "resnet152_gn"):
        from fedml_tpu.models import resnet_gn

        ctor = getattr(resnet_gn, name[: -len("_gn")])
        cpg = kw.pop("channels_per_group", 2)
        m = ctor(num_classes, channels_per_group=cpg, **kw)
        return ModelDef(
            m, input_shape, num_classes, has_batch_stats=(cpg == 0), name=name,
        )

    if name == "mobilenet":
        from fedml_tpu.models.mobilenet import MobileNet

        return ModelDef(
            MobileNet(num_classes=num_classes, **kw),
            input_shape, num_classes, has_batch_stats=True, name=name,
        )

    if name == "mobilenet_v3":
        from fedml_tpu.models.mobilenet import MobileNetV3

        return ModelDef(
            MobileNetV3(num_classes=num_classes, **kw),
            input_shape, num_classes,
            has_batch_stats=True, has_dropout=True, name=name,
        )

    if name in ("vgg11", "vgg13", "vgg16", "vgg19",
                "vgg11_bn", "vgg13_bn", "vgg16_bn", "vgg19_bn"):
        from fedml_tpu.models import vgg as vgg_mod

        bn = name.endswith("_bn")
        base = name[:-3] if bn else name
        m = getattr(vgg_mod, base)(num_classes=num_classes, batch_norm=bn)
        return ModelDef(
            m, input_shape, num_classes,
            has_batch_stats=bn, has_dropout=True, name=name,
        )

    if name == "segnet":
        from fedml_tpu.models.segnet import EncoderDecoder

        return ModelDef(
            EncoderDecoder(num_classes=num_classes, **kw),
            input_shape, num_classes, has_batch_stats=True, name=name,
        )

    if name == "darts":
        from fedml_tpu.models.darts import DARTSNetwork

        return ModelDef(
            DARTSNetwork(num_classes=num_classes, **kw),
            input_shape, num_classes, has_batch_stats=True, name=name,
        )

    if name == "mnistgan":
        from fedml_tpu.algorithms.fedgan import make_gan_model_def

        return make_gan_model_def(**kw)

    if name == "efficientnet":
        from fedml_tpu.models.efficientnet import EfficientNet

        return ModelDef(
            EfficientNet(num_classes=num_classes, **kw),
            input_shape, num_classes,
            has_batch_stats=True, has_dropout=True, name=name,
        )

    raise KeyError(
        f"unknown model {model_name!r}; available: lr, cnn, cnn_dropout, rnn, "
        "transformer, decoder, resnet56, resnet110, resnet18_gn..resnet152_gn, "
        "mobilenet, mobilenet_v3, vgg11..vgg19(_bn), efficientnet, segnet, "
        "darts, mnistgan"
    )


def _with_pretrained(model: ModelDef, path: str) -> ModelDef:
    """Wrap ``model.init`` to return checkpoint weights: ``.pth`` goes through
    the torch importer, ``.npz`` through the save_pretrained recipe
    (models/pretrained.py)."""
    import dataclasses

    from fedml_tpu.models import pretrained as P

    inner_init = model.init

    def init(rng):
        template = inner_init(rng)
        if str(path).endswith(".pth"):
            return P.load_torch_checkpoint(str(path), template)
        return P.load_pretrained(str(path), template)

    loaded = dataclasses.replace(model)
    loaded.init = init  # type: ignore[method-assign]
    return loaded
