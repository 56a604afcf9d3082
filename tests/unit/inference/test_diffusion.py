"""Diffusers/CLIP serving surface.

- CLIP text encoder: numerical parity against the real torch ``CLIPTextModel``.
- UNet/VAE: the diffusers package is not installed, so the state dicts are
  SYNTHESIZED here in diffusers naming/shapes (an independent transcription of
  the format; ``convert_*`` raises on any unmatched/missing/mismatched tensor,
  so a drift between this contract and the flax modules fails loudly).
- txt2img: the whole denoising loop compiles as one program and returns finite
  images in [0, 1].
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.diffusion_engine import (DiffusionInferenceEngine,
                                                      init_diffusion_inference)
from deepspeed_tpu.models.diffusion import (CLIPTextEncoder, UNet2DCondition,
                                            UNetConfig, VAEConfig, VAEDecoder)

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

UNET = UNetConfig(sample_size=8, in_channels=4, out_channels=4,
                  block_out_channels=(32, 64), layers_per_block=1,
                  cross_attention_dim=32, attention_head_dim=4,
                  norm_num_groups=8, dtype=jnp.float32)
VAE = VAEConfig(latent_channels=4, out_channels=3,
                block_out_channels=(32, 64), layers_per_block=1,
                norm_num_groups=8, dtype=jnp.float32)


# ------------------------------------------------- synthesized diffusers dicts
def _t(rng, *shape):
    return torch.tensor(rng.standard_normal(shape).astype(np.float32) * 0.05)


def _conv(sd, rng, key, cin, cout, k=3):
    sd[f"{key}.weight"] = _t(rng, cout, cin, k, k)
    sd[f"{key}.bias"] = _t(rng, cout)


def _linear(sd, rng, key, cin, cout, bias=True):
    sd[f"{key}.weight"] = _t(rng, cout, cin)
    if bias:
        sd[f"{key}.bias"] = _t(rng, cout)


def _norm(sd, rng, key, c):
    sd[f"{key}.weight"] = _t(rng, c)
    sd[f"{key}.bias"] = _t(rng, c)


def _resnet(sd, rng, key, cin, cout, tdim=None):
    _norm(sd, rng, f"{key}.norm1", cin)
    _conv(sd, rng, f"{key}.conv1", cin, cout)
    if tdim is not None:
        _linear(sd, rng, f"{key}.time_emb_proj", tdim, cout)
    _norm(sd, rng, f"{key}.norm2", cout)
    _conv(sd, rng, f"{key}.conv2", cout, cout)
    if cin != cout:
        _conv(sd, rng, f"{key}.conv_shortcut", cin, cout, k=1)


def _attention_block(sd, rng, key, c, ctx_dim):
    _norm(sd, rng, f"{key}.norm", c)
    _conv(sd, rng, f"{key}.proj_in", c, c, k=1)
    _conv(sd, rng, f"{key}.proj_out", c, c, k=1)
    tb = f"{key}.transformer_blocks.0"
    for n in ("norm1", "norm2", "norm3"):
        _norm(sd, rng, f"{tb}.{n}", c)
    for attn, kv in (("attn1", c), ("attn2", ctx_dim)):
        _linear(sd, rng, f"{tb}.{attn}.to_q", c, c, bias=False)
        _linear(sd, rng, f"{tb}.{attn}.to_k", kv, c, bias=False)
        _linear(sd, rng, f"{tb}.{attn}.to_v", kv, c, bias=False)
        _linear(sd, rng, f"{tb}.{attn}.to_out.0", c, c)
    _linear(sd, rng, f"{tb}.ff.net.0.proj", c, 8 * c)
    _linear(sd, rng, f"{tb}.ff.net.2", 4 * c, c)


def synth_unet_sd(cfg: UNetConfig, seed=0):
    """UNet2DConditionModel state dict in diffusers naming (SD-1.x topology)."""
    rng = np.random.RandomState(seed)
    sd = {}
    chs = cfg.block_out_channels
    tdim = 4 * chs[0]
    _linear(sd, rng, "time_embedding.linear_1", chs[0], tdim)
    _linear(sd, rng, "time_embedding.linear_2", tdim, tdim)
    _conv(sd, rng, "conv_in", cfg.in_channels, chs[0])
    prev = chs[0]
    for bi, ch in enumerate(chs):
        attn = bi < len(chs) - 1
        for li in range(cfg.layers_per_block):
            _resnet(sd, rng, f"down_blocks.{bi}.resnets.{li}", prev, ch, tdim)
            prev = ch
            if attn:
                _attention_block(sd, rng, f"down_blocks.{bi}.attentions.{li}",
                                 ch, cfg.cross_attention_dim)
        if bi < len(chs) - 1:
            _conv(sd, rng, f"down_blocks.{bi}.downsamplers.0.conv", ch, ch)
    _resnet(sd, rng, "mid_block.resnets.0", chs[-1], chs[-1], tdim)
    _attention_block(sd, rng, "mid_block.attentions.0", chs[-1],
                     cfg.cross_attention_dim)
    _resnet(sd, rng, "mid_block.resnets.1", chs[-1], chs[-1], tdim)

    # up path: skip stack mirrors the flax module's pops (conv_in + per-layer +
    # per-downsample outputs, consumed in reverse)
    skips = [chs[0]]
    for bi, ch in enumerate(chs):
        for li in range(cfg.layers_per_block):
            skips.append(ch)
        if bi < len(chs) - 1:
            skips.append(ch)
    h = chs[-1]
    for bi, ch in enumerate(reversed(chs)):
        attn = bi > 0
        for li in range(cfg.layers_per_block + 1):
            cin = h + skips.pop()
            _resnet(sd, rng, f"up_blocks.{bi}.resnets.{li}", cin, ch, tdim)
            h = ch
            if attn:
                _attention_block(sd, rng, f"up_blocks.{bi}.attentions.{li}",
                                 ch, cfg.cross_attention_dim)
        if bi < len(chs) - 1:
            _conv(sd, rng, f"up_blocks.{bi}.upsamplers.0.conv", ch, ch)
    _norm(sd, rng, "conv_norm_out", chs[0])
    _conv(sd, rng, "conv_out", chs[0], cfg.out_channels)
    return sd


def synth_vae_sd(cfg: VAEConfig, seed=1):
    """AutoencoderKL state dict (decoder half + post_quant_conv) + dummy encoder
    tensors (which conversion must skip)."""
    rng = np.random.RandomState(seed)
    sd = {}
    chs = cfg.block_out_channels
    _conv(sd, rng, "post_quant_conv", cfg.latent_channels, cfg.latent_channels,
          k=1)
    _conv(sd, rng, "decoder.conv_in", cfg.latent_channels, chs[-1])
    _resnet(sd, rng, "decoder.mid_block.resnets.0", chs[-1], chs[-1])
    _resnet(sd, rng, "decoder.mid_block.resnets.1", chs[-1], chs[-1])
    a = "decoder.mid_block.attentions.0"
    _norm(sd, rng, f"{a}.group_norm", chs[-1])
    _linear(sd, rng, f"{a}.to_q", chs[-1], chs[-1], bias=False)
    _linear(sd, rng, f"{a}.to_k", chs[-1], chs[-1], bias=False)
    _linear(sd, rng, f"{a}.to_v", chs[-1], chs[-1], bias=False)
    _linear(sd, rng, f"{a}.to_out.0", chs[-1], chs[-1])
    h = chs[-1]
    for bi, ch in enumerate(reversed(chs)):
        for li in range(cfg.layers_per_block + 1):
            _resnet(sd, rng, f"decoder.up_blocks.{bi}.resnets.{li}", h, ch)
            h = ch
        if bi < len(chs) - 1:
            _conv(sd, rng, f"decoder.up_blocks.{bi}.upsamplers.0.conv", ch, ch)
    _norm(sd, rng, "decoder.conv_norm_out", chs[0])
    _conv(sd, rng, "decoder.conv_out", chs[0], cfg.out_channels)
    sd["encoder.conv_in.weight"] = _t(rng, chs[0], 3, 3, 3)   # must be skipped
    sd["quant_conv.weight"] = _t(rng, 8, 8, 1, 1)
    return sd


def _tiny_clip():
    cfg = transformers.CLIPTextConfig(
        vocab_size=99, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=16)
    m = transformers.CLIPTextModel(cfg)
    m.eval()
    return m


# ------------------------------------------------------------------- the tests
class TestCLIPParity:
    def test_clip_matches_hf(self):
        from deepspeed_tpu.module_inject.diffusers_policies import \
            convert_clip_text
        m = _tiny_clip()
        cfg, params = convert_clip_text(m)
        cfg.dtype = jnp.float32
        ids = np.random.RandomState(0).randint(0, 99, size=(2, 12))
        ours = CLIPTextEncoder(cfg).apply({"params": params},
                                          jnp.asarray(ids, jnp.int32))
        with torch.no_grad():
            ref = m(input_ids=torch.tensor(ids)).last_hidden_state.numpy()
        np.testing.assert_allclose(np.asarray(ours), ref, rtol=2e-4, atol=2e-4)


    def test_clip_gelu_act_matches_hf(self):
        """SD-2.x-style text encoders use hidden_act='gelu' — the converted
        module must follow the config, not hardcode quick-gelu."""
        from deepspeed_tpu.module_inject.diffusers_policies import \
            convert_clip_text
        cfg = transformers.CLIPTextConfig(
            vocab_size=99, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=4, intermediate_size=64,
            max_position_embeddings=16, hidden_act="gelu")
        m = transformers.CLIPTextModel(cfg)
        m.eval()
        ours_cfg, params = convert_clip_text(m)
        assert ours_cfg.act == "gelu"
        ours_cfg.dtype = jnp.float32
        ids = np.random.RandomState(3).randint(0, 99, size=(2, 12))
        ours = CLIPTextEncoder(ours_cfg).apply({"params": params},
                                               jnp.asarray(ids, jnp.int32))
        with torch.no_grad():
            ref = m(input_ids=torch.tensor(ids)).last_hidden_state.numpy()
        np.testing.assert_allclose(np.asarray(ours), ref, rtol=2e-4, atol=2e-4)


class TestConversionContract:
    def test_unet_converts_and_runs(self):
        from deepspeed_tpu.module_inject.diffusers_policies import \
            convert_unet_state_dict
        sd = synth_unet_sd(UNET)
        params = convert_unet_state_dict(sd, UNET)
        # a marked tensor lands transposed in the right leaf
        w = sd["down_blocks.0.attentions.0.transformer_blocks.0.attn1.to_q"
               ".weight"].numpy()
        got = np.asarray(params["down_blocks_0_attentions_0"]
                         ["transformer_blocks_0"]["attn1"]["to_q"]["kernel"])
        np.testing.assert_array_equal(got, w.T)
        out = UNet2DCondition(UNET).apply(
            {"params": params},
            jnp.zeros((1, 8, 8, 4)), jnp.array([10], jnp.int32),
            jnp.zeros((1, 6, 32)))
        assert out.shape == (1, 8, 8, 4)
        assert np.isfinite(np.asarray(out)).all()

    def test_unet_conversion_rejects_drift(self):
        from deepspeed_tpu.module_inject.diffusers_policies import \
            convert_unet_state_dict
        sd = synth_unet_sd(UNET)
        sd["down_blocks.9.bogus.weight"] = torch.zeros(3, 3)
        with pytest.raises(ValueError, match="unmatched torch keys"):
            convert_unet_state_dict(sd, UNET)
        sd = synth_unet_sd(UNET)
        del sd["conv_out.bias"]
        with pytest.raises(ValueError, match="missing flax params"):
            convert_unet_state_dict(sd, UNET)

    def test_vae_converts_and_runs(self):
        from deepspeed_tpu.module_inject.diffusers_policies import \
            convert_vae_decoder_state_dict
        params = convert_vae_decoder_state_dict(synth_vae_sd(VAE), VAE)
        img = VAEDecoder(VAE).apply({"params": params},
                                    jnp.zeros((1, 8, 8, 4)))
        assert img.shape == (1, 16, 16, 3)   # len(chs)-1 = 1 upsample: 8 → 16
        assert np.isfinite(np.asarray(img)).all()


class TestTxt2Img:
    def test_txt2img_loop_compiles_and_runs(self):
        engine = init_diffusion_inference(
            synth_unet_sd(UNET), _tiny_clip(), synth_vae_sd(VAE),
            unet_config=UNET, vae_config=VAE)
        ids = np.random.RandomState(1).randint(0, 99, size=(1, 12))
        img = engine.generate(ids, steps=3, guidance_scale=5.0, seed=0)
        assert img.shape == (1, 16, 16, 3)
        assert np.isfinite(img).all()
        assert img.min() >= 0.0 and img.max() <= 1.0
        # deterministic per seed
        img2 = engine.generate(ids, steps=3, guidance_scale=5.0, seed=0)
        np.testing.assert_array_equal(img, img2)

    def test_txt2img_tp2_matches_tp1(self, eight_devices):
        """UNet/CLIP attention kernels shard over the tensor axis and the
        images match the unsharded engine."""
        from deepspeed_tpu.parallel.mesh import MeshSpec
        clip = _tiny_clip()
        unet_sd, vae_sd = synth_unet_sd(UNET), synth_vae_sd(VAE)
        ids = np.random.RandomState(2).randint(0, 99, size=(1, 12))
        e1 = init_diffusion_inference(unet_sd, clip, vae_sd, unet_config=UNET,
                                      vae_config=VAE)
        img1 = e1.generate(ids, steps=2, seed=0)
        e2 = init_diffusion_inference(
            unet_sd, clip, vae_sd, unet_config=UNET, vae_config=VAE,
            mesh_spec=MeshSpec({"tensor": 2}, eight_devices[:2]))
        qk = e2.params["unet"]["mid_block_attentions_0"]["transformer_blocks_0"]\
            ["attn1"]["to_q"]["kernel"]
        assert "tensor" in str(qk.sharding.spec), qk.sharding.spec
        img2 = e2.generate(ids, steps=2, seed=0)
        np.testing.assert_allclose(img2, img1, atol=2e-3)

    def test_engine_does_not_clobber_installed_mesh(self, eight_devices):
        """ISSUE 1 satellite: constructing a diffusion engine must not swap out
        another engine's active global mesh — its own shardings are explicit."""
        from deepspeed_tpu.parallel.mesh import (MeshSpec, get_global_mesh,
                                                 set_global_mesh)
        training_mesh = MeshSpec({"data": 2}, eight_devices[:2])
        set_global_mesh(training_mesh)
        init_diffusion_inference(
            synth_unet_sd(UNET), _tiny_clip(), synth_vae_sd(VAE),
            unet_config=UNET, vae_config=VAE,
            mesh_spec=MeshSpec({"tensor": 2}, eight_devices[2:4]))
        assert get_global_mesh() is training_mesh
        # with the slot free, the engine's mesh installs as before
        set_global_mesh(None)
        e = init_diffusion_inference(
            synth_unet_sd(UNET), _tiny_clip(), synth_vae_sd(VAE),
            unet_config=UNET, vae_config=VAE,
            mesh_spec=MeshSpec({"tensor": 2}, eight_devices[:2]))
        assert get_global_mesh() is e.mesh_spec
