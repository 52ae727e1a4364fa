"""Contrib operators (parity: src/operator/contrib/ — SURVEY.md §2.2).

ctc_loss (optax XLA), fft/ifft (cuFFT → jnp.fft), quantize/dequantize,
count_sketch, MultiBoxPrior/Target/Detection (SSD detection ops — the
reference's hand-written CUDA kernels become vectorized jax; non-max
suppression uses a fixed-iteration lax loop, XLA-compilable).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..base import Arg, MXNetError
from .registry import register


@register("_contrib_ctc_loss", input_names=("data", "label", "data_lengths",
                                            "label_lengths"),
          aliases=("ctc_loss", "CTCLoss"),
          args=[Arg("use_data_lengths", bool, False),
                Arg("use_label_lengths", bool, False),
                Arg("blank_label", str, "first")])
def _ctc_loss(p, data, label, data_lengths=None, label_lengths=None):
    """Parity: contrib/ctc_loss.cc.  data: (T, N, C) activations (pre-softmax),
    label: (N, L) padded with 0/-1; optional per-sequence lengths gated by
    use_data_lengths / use_label_lengths (reference inputs 3 and 4)."""
    if (p["use_label_lengths"] and not p["use_data_lengths"]
            and label_lengths is None):
        # positional call with the unused data_lengths slot elided (symbol
        # graphs bind inputs positionally; the slot list is gated on the
        # use_* flags) — the third input IS label_lengths
        data_lengths, label_lengths = None, data_lengths
    T, N, C = data.shape
    logits = jnp.transpose(data, (1, 0, 2))  # (N,T,C)
    labels = label.astype(jnp.int32)
    logit_pad = jnp.zeros((N, T), jnp.float32)
    if p["use_data_lengths"] and data_lengths is not None:
        steps = jnp.arange(T)[None, :]
        logit_pad = (steps >= data_lengths[:, None]).astype(jnp.float32)
    if p["blank_label"] == "first":
        # mxnet 'first': channel 0 is blank, real labels are 1..C-1 —
        # matches optax blank_id=0 with labels kept as-is
        lab_valid = labels > 0
        blank = 0
    else:
        lab_valid = labels >= 0
        blank = C - 1
    if p["use_label_lengths"] and label_lengths is not None:
        steps = jnp.arange(labels.shape[1])[None, :]
        lab_valid = steps < label_lengths[:, None].astype(jnp.int32)
    lab = jnp.where(lab_valid, labels, 0)
    return _ctc_scan(logits, logit_pad, lab,
                     (~lab_valid).astype(jnp.float32), blank)


@functools.partial(jax.jit, static_argnums=4)
def _ctc_scan(logits, logit_pad, labels, label_pad, blank):
    # one jitted callable for the process: optax's forward recursion is a
    # lax.scan over a fresh closure, and a scan bound eagerly (the
    # recorded nd path differentiates op.fn with jax.vjp outside any
    # jit) is compiled anew at every call, forward and transpose
    import optax
    return optax.ctc_loss(logits, logit_pad, labels, label_pad,
                          blank_id=blank)


@register("_contrib_fft", input_names=("data",), aliases=("fft",),
          args=[Arg("compute_size", int, 128)])
def _fft(p, x):
    """Parity: contrib/fft.cc — output interleaves real/imag on last dim."""
    out = jnp.fft.fft(x, axis=-1)
    return jnp.stack([out.real, out.imag], axis=-1).reshape(
        x.shape[:-1] + (2 * x.shape[-1],)).astype(x.dtype)


@register("_contrib_ifft", input_names=("data",), aliases=("ifft",),
          args=[Arg("compute_size", int, 128)])
def _ifft(p, x):
    n = x.shape[-1] // 2
    comp = x.reshape(x.shape[:-1] + (n, 2))
    z = comp[..., 0] + 1j * comp[..., 1]
    return jnp.fft.ifft(z, axis=-1).real.astype(x.dtype) * n


@register("_contrib_quantize", input_names=("data", "min_range", "max_range"),
          num_outputs=3, differentiable=False,
          args=[Arg("out_type", str, "uint8")])
def _quantize(p, data, min_range, max_range):
    """Parity: contrib/quantize.cc — affine quantization to uint8/int8."""
    if p["out_type"] == "uint8":
        qmin, qmax, dt = 0.0, 255.0, jnp.uint8
    else:
        qmin, qmax, dt = -127.0, 127.0, jnp.int8
    scale = (qmax - qmin) / jnp.maximum(max_range - min_range, 1e-8)
    q = jnp.clip(jnp.round((data - min_range) * scale + qmin), qmin, qmax)
    return q.astype(dt), min_range, max_range


@register("_contrib_dequantize", input_names=("data", "min_range", "max_range"),
          differentiable=False, args=[Arg("out_type", str, "float32")])
def _dequantize(p, data, min_range, max_range):
    if data.dtype == jnp.uint8:
        qmin, qmax = 0.0, 255.0
    else:
        qmin, qmax = -127.0, 127.0
    scale = (max_range - min_range) / (qmax - qmin)
    return (data.astype(jnp.float32) - qmin) * scale + min_range


@register("_contrib_count_sketch", input_names=("data", "h", "s"),
          args=[Arg("out_dim", int, required=True),
                Arg("processing_batch_size", int, 32)])
def _count_sketch(p, data, h, s):
    """Parity: contrib/count_sketch.cc — random-projection sketch."""
    n, d = data.shape
    out_dim = p["out_dim"]
    hh = h.reshape(-1).astype(jnp.int32)[:d]
    ss = s.reshape(-1)[:d]
    vals = data * ss[None, :]
    out = jnp.zeros((n, out_dim), data.dtype)
    return out.at[:, hh].add(vals)


# ---------------------------------------------------------------------------
# SSD multibox ops (parity: src/operator/contrib/multibox_*.cc)
# ---------------------------------------------------------------------------
@register("_contrib_MultiBoxPrior", input_names=("data",),
          aliases=("MultiBoxPrior",), differentiable=False,
          args=[Arg("sizes", "floats", (1.0,)), Arg("ratios", "floats", (1.0,)),
                Arg("clip", bool, False), Arg("steps", "floats", (-1.0, -1.0)),
                Arg("offsets", "floats", (0.5, 0.5))])
def _multibox_prior(p, data):
    """Anchor generation (parity: multibox_prior.cc).  data: (N,C,H,W) →
    (1, H*W*num_anchors, 4) corner-format anchors in [0,1]."""
    H, W = data.shape[2], data.shape[3]
    sizes = [float(s) for s in p["sizes"]]
    ratios = [float(r) for r in p["ratios"]]
    step_y, step_x = p["steps"]
    step_y = 1.0 / H if step_y <= 0 else step_y
    step_x = 1.0 / W if step_x <= 0 else step_x
    off_y, off_x = p["offsets"]
    cy = (jnp.arange(H) + off_y) * step_y
    cx = (jnp.arange(W) + off_x) * step_x
    cyx = jnp.stack(jnp.meshgrid(cy, cx, indexing="ij"), -1).reshape(-1, 2)
    whs = []
    # mxnet convention: sizes[0] with each ratio? No — (size,1.0) for each
    # size + (sizes[0], ratio) for each extra ratio → len(sizes)+len(ratios)-1
    for s in sizes:
        whs.append((s * (H / W) ** 0.5 if False else s, s))
    base = sizes[0]
    for r in ratios[1:]:
        whs.append((base * (r ** 0.5), base / (r ** 0.5)))
    whs = jnp.asarray(whs)  # (A, 2) = (w, h)
    A = whs.shape[0]
    centers = jnp.repeat(cyx, A, axis=0)  # (H*W*A, 2) [cy, cx]
    wh = jnp.tile(whs, (H * W, 1))
    xmin = centers[:, 1] - wh[:, 0] / 2
    ymin = centers[:, 0] - wh[:, 1] / 2
    xmax = centers[:, 1] + wh[:, 0] / 2
    ymax = centers[:, 0] + wh[:, 1] / 2
    out = jnp.stack([xmin, ymin, xmax, ymax], axis=-1)
    if p["clip"]:
        out = jnp.clip(out, 0.0, 1.0)
    return out[None]


def _iou_corner(a, b):
    """IoU between (...,4) corner boxes a and b."""
    ix1 = jnp.maximum(a[..., 0], b[..., 0])
    iy1 = jnp.maximum(a[..., 1], b[..., 1])
    ix2 = jnp.minimum(a[..., 2], b[..., 2])
    iy2 = jnp.minimum(a[..., 3], b[..., 3])
    iw = jnp.maximum(ix2 - ix1, 0)
    ih = jnp.maximum(iy2 - iy1, 0)
    inter = iw * ih
    area_a = jnp.maximum(a[..., 2] - a[..., 0], 0) * \
        jnp.maximum(a[..., 3] - a[..., 1], 0)
    area_b = jnp.maximum(b[..., 2] - b[..., 0], 0) * \
        jnp.maximum(b[..., 3] - b[..., 1], 0)
    return inter / jnp.maximum(area_a + area_b - inter, 1e-10)


@register("_contrib_MultiBoxTarget",
          input_names=("anchor", "label", "cls_pred"),
          aliases=("MultiBoxTarget",), num_outputs=3, differentiable=False,
          args=[Arg("overlap_threshold", float, 0.5),
                Arg("ignore_label", float, -1.0),
                Arg("negative_mining_ratio", float, -1.0),
                Arg("negative_mining_thresh", float, 0.5),
                Arg("minimum_negative_samples", int, 0),
                Arg("variances", "floats", (0.1, 0.1, 0.2, 0.2))])
def _multibox_target(p, anchor, label, cls_pred):
    """Anchor→GT matching + regression targets (parity: multibox_target.cc).

    anchor: (1,A,4); label: (N,M,5) [cls,x1,y1,x2,y2] (cls<0 = pad);
    cls_pred: (N, num_cls+1, A).  Returns (loc_target (N,A*4),
    loc_mask (N,A*4), cls_target (N,A))."""
    anchors = anchor[0]  # (A,4)
    A = anchors.shape[0]
    vx, vy, vw, vh = p["variances"]
    thresh = p["overlap_threshold"]

    def per_sample(lab):
        valid = lab[:, 0] >= 0  # (M,)
        gt = lab[:, 1:5]
        ious = _iou_corner(anchors[:, None, :], gt[None, :, :])  # (A,M)
        ious = jnp.where(valid[None, :], ious, -1.0)
        best_gt = jnp.argmax(ious, axis=1)           # (A,)
        best_iou = jnp.max(ious, axis=1)
        matched = best_iou > thresh
        # ensure every valid gt owns its argmax anchor
        best_anchor = jnp.argmax(ious, axis=0)       # (M,)
        forced = jnp.zeros(A, bool).at[best_anchor].set(valid)
        forced_gt = jnp.zeros(A, jnp.int32).at[best_anchor].set(
            jnp.arange(gt.shape[0], dtype=jnp.int32))
        use_gt = jnp.where(forced, forced_gt, best_gt)
        matched = matched | forced
        g = gt[use_gt]
        # encode (corner→center) with variances
        aw = anchors[:, 2] - anchors[:, 0]
        ah = anchors[:, 3] - anchors[:, 1]
        acx = (anchors[:, 0] + anchors[:, 2]) / 2
        acy = (anchors[:, 1] + anchors[:, 3]) / 2
        gw = jnp.maximum(g[:, 2] - g[:, 0], 1e-8)
        gh = jnp.maximum(g[:, 3] - g[:, 1], 1e-8)
        gcx = (g[:, 0] + g[:, 2]) / 2
        gcy = (g[:, 1] + g[:, 3]) / 2
        tx = (gcx - acx) / jnp.maximum(aw, 1e-8) / vx
        ty = (gcy - acy) / jnp.maximum(ah, 1e-8) / vy
        tw = jnp.log(gw / jnp.maximum(aw, 1e-8)) / vw
        th = jnp.log(gh / jnp.maximum(ah, 1e-8)) / vh
        loc_t = jnp.stack([tx, ty, tw, th], axis=-1)  # (A,4)
        loc_t = jnp.where(matched[:, None], loc_t, 0.0).reshape(-1)
        loc_m = jnp.where(matched[:, None],
                          jnp.ones((A, 4)), 0.0).reshape(-1)
        cls_t = jnp.where(matched, lab[use_gt, 0] + 1, 0.0)
        return loc_t, loc_m, cls_t

    loc_t, loc_m, cls_t = jax.vmap(per_sample)(label)
    return loc_t, loc_m, cls_t


@register("_contrib_MultiBoxDetection",
          input_names=("cls_prob", "loc_pred", "anchor"),
          aliases=("MultiBoxDetection",), differentiable=False,
          args=[Arg("clip", bool, True), Arg("threshold", float, 0.01),
                Arg("background_id", int, 0), Arg("nms_threshold", float, 0.5),
                Arg("force_suppress", bool, False),
                Arg("variances", "floats", (0.1, 0.1, 0.2, 0.2)),
                Arg("nms_topk", int, -1)])
def _multibox_detection(p, cls_prob, loc_pred, anchor):
    """Decode + NMS (parity: multibox_detection.cc).  Returns
    (N, A, 6) rows [cls_id, score, x1, y1, x2, y2]; suppressed rows cls=-1."""
    anchors = anchor[0]
    A = anchors.shape[0]
    vx, vy, vw, vh = p["variances"]
    aw = anchors[:, 2] - anchors[:, 0]
    ah = anchors[:, 3] - anchors[:, 1]
    acx = (anchors[:, 0] + anchors[:, 2]) / 2
    acy = (anchors[:, 1] + anchors[:, 3]) / 2

    def per_sample(probs, locs):
        loc = locs.reshape(A, 4)
        cx = loc[:, 0] * vx * aw + acx
        cy = loc[:, 1] * vy * ah + acy
        w = jnp.exp(loc[:, 2] * vw) * aw
        h = jnp.exp(loc[:, 3] * vh) * ah
        boxes = jnp.stack([cx - w / 2, cy - h / 2, cx + w / 2, cy + h / 2], -1)
        if p["clip"]:
            boxes = jnp.clip(boxes, 0.0, 1.0)
        # class scores, excluding background
        scores = probs[1:] if p["background_id"] == 0 else \
            jnp.concatenate([probs[:p["background_id"]],
                             probs[p["background_id"] + 1:]])
        cls_id = jnp.argmax(scores, axis=0).astype(jnp.float32)  # (A,)
        score = jnp.max(scores, axis=0)
        keep = score > p["threshold"]
        cls_id = jnp.where(keep, cls_id, -1.0)
        # greedy NMS, fixed iterations over score-sorted order
        order = jnp.argsort(-score)
        boxes_s = boxes[order]
        cls_s = cls_id[order]
        score_s = score[order]
        alive = cls_s >= 0

        def body(i, alive):
            box_i = boxes_s[i]
            cls_i = cls_s[i]
            this_alive = alive[i]
            ious = _iou_corner(box_i[None], boxes_s)
            same = (cls_s == cls_i) | bool(p["force_suppress"])
            sup = (ious > p["nms_threshold"]) & same & \
                (jnp.arange(A) > i) & this_alive
            return alive & ~sup

        alive = lax.fori_loop(0, A, body, alive)
        out = jnp.concatenate(
            [jnp.where(alive, cls_s, -1.0)[:, None], score_s[:, None],
             boxes_s], axis=1)
        return out

    return jax.vmap(per_sample)(cls_prob, loc_pred.reshape(
        cls_prob.shape[0], -1))


# ---------------------------------------------------------------------------
# RPN proposals (parity: src/operator/contrib/proposal.cc / multi_proposal.cc)
# ---------------------------------------------------------------------------
def _gen_base_anchors(scales, ratios, base_size):
    """Anchors centered at (base/2, base/2), corner format, in pixels."""
    anchors = []
    cx = cy = (base_size - 1) / 2.0
    area = float(base_size * base_size)
    for r in ratios:
        w = round((area / r) ** 0.5)
        h = round(w * r)
        for s in scales:
            ws, hs = w * s, h * s
            anchors.append([cx - (ws - 1) / 2, cy - (hs - 1) / 2,
                            cx + (ws - 1) / 2, cy + (hs - 1) / 2])
    return jnp.asarray(anchors, jnp.float32)


@register("_contrib_Proposal", input_names=("cls_prob", "bbox_pred", "im_info"),
          aliases=("Proposal", "_contrib_MultiProposal", "MultiProposal"),
          differentiable=False,
          args=[Arg("rpn_pre_nms_top_n", int, 6000),
                Arg("rpn_post_nms_top_n", int, 300),
                Arg("threshold", float, 0.7),
                Arg("rpn_min_size", int, 16),
                Arg("scales", "floats", (4.0, 8.0, 16.0, 32.0)),
                Arg("ratios", "floats", (0.5, 1.0, 2.0)),
                Arg("feature_stride", int, 16),
                Arg("output_score", bool, False),
                Arg("iou_loss", bool, False)])
def _proposal(p, cls_prob, bbox_pred, im_info):
    """RPN proposal generation (parity: proposal.cc behavior): decode
    per-anchor bbox deltas, clip to image, filter small boxes, NMS, take
    top-k.  Static shapes: output (N * post_nms_top_n, 5) rois
    [batch_idx, x1, y1, x2, y2], padded by repeating the best roi."""
    N, _, H, W = cls_prob.shape
    stride = p["feature_stride"]
    base = _gen_base_anchors(p["scales"], p["ratios"], stride)  # (A,4)
    A = base.shape[0]
    shift_x = jnp.arange(W) * stride
    shift_y = jnp.arange(H) * stride
    sx, sy = jnp.meshgrid(shift_x, shift_y)  # (H,W)
    shifts = jnp.stack([sx, sy, sx, sy], axis=-1).reshape(-1, 4)
    anchors = (base[None, :, :] + shifts[:, None, :]).reshape(-1, 4)  # (H*W*A,4)
    K = anchors.shape[0]
    pre_n = min(p["rpn_pre_nms_top_n"], K)
    post_n = p["rpn_post_nms_top_n"]

    def per_image(scores_hw, deltas_hw, info):
        # scores: (2A,H,W) → fg scores (A,H,W) → (H*W*A,)
        fg = scores_hw[A:].transpose(1, 2, 0).reshape(-1)
        d = deltas_hw.transpose(1, 2, 0).reshape(-1, 4)  # (H*W*A,4)
        aw = anchors[:, 2] - anchors[:, 0] + 1
        ah = anchors[:, 3] - anchors[:, 1] + 1
        acx = anchors[:, 0] + 0.5 * (aw - 1)
        acy = anchors[:, 1] + 0.5 * (ah - 1)
        cx = d[:, 0] * aw + acx
        cy = d[:, 1] * ah + acy
        w = jnp.exp(jnp.clip(d[:, 2], -10, 10)) * aw
        h = jnp.exp(jnp.clip(d[:, 3], -10, 10)) * ah
        x1 = jnp.clip(cx - 0.5 * (w - 1), 0, info[1] - 1)
        y1 = jnp.clip(cy - 0.5 * (h - 1), 0, info[0] - 1)
        x2 = jnp.clip(cx + 0.5 * (w - 1), 0, info[1] - 1)
        y2 = jnp.clip(cy + 0.5 * (h - 1), 0, info[0] - 1)
        boxes = jnp.stack([x1, y1, x2, y2], axis=-1)
        min_size = p["rpn_min_size"] * info[2]
        valid = ((x2 - x1 + 1) >= min_size) & ((y2 - y1 + 1) >= min_size)
        fg = jnp.where(valid, fg, -1.0)
        order = jnp.argsort(-fg)[:pre_n]
        boxes_s = boxes[order]
        score_s = fg[order]
        alive = score_s > -1.0

        def iou_pixel(a, b):
            # proposal.cc integer-pixel convention: width = x2 - x1 + 1
            ix1 = jnp.maximum(a[..., 0], b[..., 0])
            iy1 = jnp.maximum(a[..., 1], b[..., 1])
            ix2 = jnp.minimum(a[..., 2], b[..., 2])
            iy2 = jnp.minimum(a[..., 3], b[..., 3])
            inter = jnp.maximum(ix2 - ix1 + 1, 0) * \
                jnp.maximum(iy2 - iy1 + 1, 0)
            area_a = (a[..., 2] - a[..., 0] + 1) * (a[..., 3] - a[..., 1] + 1)
            area_b = (b[..., 2] - b[..., 0] + 1) * (b[..., 3] - b[..., 1] + 1)
            return inter / jnp.maximum(area_a + area_b - inter, 1e-10)

        def body(i, alive):
            ious = iou_pixel(boxes_s[i][None], boxes_s)
            sup = (ious > p["threshold"]) & (jnp.arange(pre_n) > i) & alive[i]
            return alive & ~sup

        alive = lax.fori_loop(0, pre_n, body, alive)
        rank = jnp.where(alive, jnp.arange(pre_n), pre_n)
        keep = jnp.argsort(rank)[:post_n]
        kept_boxes = boxes_s[keep]
        kept_scores = jnp.where(alive[keep], score_s[keep], 0.0)
        # pad slots past the kept count with the top roi (reference pads too)
        pad_mask = (jnp.arange(post_n) < alive.sum())[:, None]
        kept_boxes = jnp.where(pad_mask, kept_boxes, kept_boxes[0])
        return kept_boxes, kept_scores

    boxes, scores = jax.vmap(per_image)(cls_prob, bbox_pred, im_info)
    batch_idx = jnp.repeat(jnp.arange(N, dtype=jnp.float32), post_n)
    rois = jnp.concatenate([batch_idx[:, None],
                            boxes.reshape(-1, 4)], axis=1)
    if p["output_score"]:
        return rois, scores.reshape(-1, 1)
    return rois


# ---------------------------------------------------------------------------
# Deformable ops (parity: src/operator/contrib/deformable_convolution.cc,
# deformable_psroi_pooling.cc) — bilinear sampling via map_coordinates
# ---------------------------------------------------------------------------
@register("_contrib_DeformableConvolution",
          input_names=("data", "offset", "weight", "bias"),
          aliases=("DeformableConvolution",),
          args=[Arg("kernel", "shape", required=True),
                Arg("stride", "shape", (1, 1)), Arg("dilate", "shape", (1, 1)),
                Arg("pad", "shape", (0, 0)), Arg("num_filter", int, required=True),
                Arg("num_group", int, 1), Arg("num_deformable_group", int, 1),
                Arg("no_bias", bool, False)])
def _deformable_conv(p, data, offset, weight, bias=None):
    """Deformable conv v1: per-position sampling offsets bend the kernel
    grid; bilinear-sampled columns contract with the weight on the MXU."""
    kh, kw = p["kernel"]
    sh, sw = p["stride"] or (1, 1)
    dh, dw = p["dilate"] or (1, 1)
    ph, pw = p["pad"] or (0, 0)
    N, C, H, W = data.shape
    G = p["num_deformable_group"]
    Ho = (H + 2 * ph - dh * (kh - 1) - 1) // sh + 1
    Wo = (W + 2 * pw - dw * (kw - 1) - 1) // sw + 1

    def sample_image(img, off):
        # img: (C,H,W); off: (2*G*kh*kw, Ho, Wo) with the reference's
        # interleaved layout: channel 2*(i*kw+j) = y, 2*(i*kw+j)+1 = x
        # (deformable_im2col convention)
        off = off.reshape(G, kh * kw, 2, Ho, Wo)
        from jax.scipy.ndimage import map_coordinates

        def sample_channel(ch_img, oy, ox):
            yy = (jnp.arange(Ho)[None, None, :, None] * sh - ph +
                  jnp.arange(kh)[:, None, None, None] * dh + oy)
            xx = (jnp.arange(Wo)[None, None, None, :] * sw - pw +
                  jnp.arange(kw)[None, :, None, None] * dw + ox)
            samp = map_coordinates(ch_img, [yy.reshape(-1), xx.reshape(-1)],
                                   order=1, mode="constant", cval=0.0)
            return samp.reshape(kh, kw, Ho, Wo)

        per_g = C // G
        groups = []
        for g in range(G):  # G is small; channels within a group vmap
            oy = off[g, :, 0].reshape(kh, kw, Ho, Wo)
            ox = off[g, :, 1].reshape(kh, kw, Ho, Wo)
            block = img[g * per_g:(g + 1) * per_g]
            groups.append(jax.vmap(sample_channel, in_axes=(0, None, None))(
                block, oy, ox))
        return jnp.concatenate(groups)  # (C,kh,kw,Ho,Wo)

    cols = jax.vmap(sample_image)(data, offset)  # (N,C,kh,kw,Ho,Wo)
    ng = p["num_group"]
    Cg = C // ng
    Fg = p["num_filter"] // ng
    cols = cols.reshape(N, ng, Cg, kh, kw, Ho, Wo)
    wgt = weight.reshape(ng, Fg, Cg, kh, kw)
    out = jnp.einsum("ngcijhw,gfcij->ngfhw", cols, wgt)
    out = out.reshape(N, p["num_filter"], Ho, Wo)
    if not p["no_bias"] and bias is not None:
        out = out + bias[None, :, None, None]
    return out


@register("khatri_rao", input_names=("args",), variadic=True)
def _khatri_rao(p, *mats):
    """Column-wise Khatri-Rao product (parity: src/operator/contrib/
    krprod.h — per-column Kronecker products): inputs (r_i, k) with a
    shared column count k → output (prod r_i, k)."""
    out = mats[0]
    for m in mats[1:]:
        out = (out[:, None, :] * m[None, :, :]).reshape(-1, m.shape[1])
    return out


@register("_contrib_DeformablePSROIPooling",
          input_names=("data", "rois", "trans"),
          aliases=("DeformablePSROIPooling",),
          args=[Arg("spatial_scale", float, required=True),
                Arg("output_dim", int, required=True),
                Arg("group_size", int, required=True),
                Arg("pooled_size", int, required=True),
                Arg("part_size", int, 0),
                Arg("sample_per_part", int, 4),
                Arg("trans_std", float, 0.0),
                Arg("no_trans", bool, False)])
def _deformable_psroi_pooling(p, data, rois, trans=None):
    """Deformable position-sensitive ROI pooling (parity:
    src/operator/contrib/deformable_psroi_pooling.cc): each pooled cell's
    sampling window shifts by a learned per-part offset
    trans[(cls*2[+1]), part_h, part_w] * trans_std * roi_size; samples
    falling outside the image are excluded from the bin average (masked
    mean).  Differentiable through the bilinear sampling and the offsets.
    """
    k = p["pooled_size"]
    D = p["output_dim"]
    gs = p["group_size"] or k
    ps = p["part_size"] or k
    S = p["sample_per_part"]
    scale = p["spatial_scale"]
    no_trans = p["no_trans"] or trans is None
    tstd = p["trans_std"]
    N, C, H, W = data.shape
    ncls = 1 if no_trans else trans.shape[1] // 2
    per_cls = D // ncls
    from jax.scipy.ndimage import map_coordinates

    def per_roi(roi, tr):
        b = roi[0].astype(jnp.int32)
        # reference rounds roi coords then offsets by half a pixel
        x1 = jnp.round(roi[1]) * scale - 0.5
        y1 = jnp.round(roi[2]) * scale - 0.5
        x2 = (jnp.round(roi[3]) + 1.0) * scale - 0.5
        y2 = (jnp.round(roi[4]) + 1.0) * scale - 0.5
        rw = jnp.maximum(x2 - x1, 0.1)
        rh = jnp.maximum(y2 - y1, 0.1)
        bw, bh = rw / k, rh / k
        sub_w, sub_h = bw / S, bh / S
        img = data[b]

        def pool_channel(d):
            cls = d // per_cls

            def cell(i, j):
                if no_trans:
                    dx = dy = 0.0
                else:
                    pi = i * ps // k
                    pj = j * ps // k
                    dx = tr[cls * 2, pi, pj] * tstd * rw
                    dy = tr[cls * 2 + 1, pi, pj] * tstd * rh
                ws = j * bw + x1 + dx
                hs = i * bh + y1 + dy
                # reference kernel samples at sub-bin LEFT edges
                # (deformable_psroi_pooling.cu: w = wstart + iw*sub_bin)
                sx = ws + jnp.arange(S) * sub_w
                sy = hs + jnp.arange(S) * sub_h
                gy = jnp.repeat(sy, S)
                gx = jnp.tile(sx, S)
                valid = ((gx > -0.5) & (gx < W - 0.5) &
                         (gy > -0.5) & (gy < H - 0.5))
                gh = i * gs // k
                gw = j * gs // k
                ch = (d * gs + gh) * gs + gw
                vals = map_coordinates(img[ch],
                                       [jnp.clip(gy, 0, H - 1),
                                        jnp.clip(gx, 0, W - 1)],
                                       order=1, mode="nearest")
                cnt = jnp.maximum(valid.sum(), 1)
                return jnp.where(valid, vals, 0.0).sum() / cnt

            return jnp.stack([jnp.stack([cell(i, j) for j in range(k)])
                              for i in range(k)])

        return jnp.stack([pool_channel(d) for d in range(D)])

    if no_trans:
        tr0 = jnp.zeros((rois.shape[0], 2, ps, ps), data.dtype)
    else:
        tr0 = trans
    return jax.vmap(per_roi)(rois, tr0)


@register("_contrib_PSROIPooling", input_names=("data", "rois"),
          aliases=("PSROIPooling",),
          args=[Arg("spatial_scale", float, required=True),
                Arg("output_dim", int, required=True),
                Arg("pooled_size", int, required=True),
                Arg("group_size", int, 0)])
def _psroi_pooling(p, data, rois):
    """Position-sensitive ROI pooling (R-FCN): score-map channel
    (ctop*gs+gh)*gs+gw selected per output cell (gh/gw = the cell's group),
    average-pooled within each bin; differentiable through the bilinear
    sampling (the reference implements an explicit backward)."""
    k = p["pooled_size"]
    D = p["output_dim"]
    gs = p["group_size"] or k
    scale = p["spatial_scale"]
    N, C, H, W = data.shape

    def per_roi(roi):
        b = roi[0].astype(jnp.int32)
        x1, y1, x2, y2 = roi[1] * scale, roi[2] * scale, \
            roi[3] * scale, roi[4] * scale
        rw = jnp.maximum(x2 - x1, 0.1)
        rh = jnp.maximum(y2 - y1, 0.1)
        bin_w, bin_h = rw / k, rh / k
        S = 4  # samples per bin edge
        ys = y1 + (jnp.arange(k)[:, None] + (jnp.arange(S)[None, :] + 0.5) / S) * bin_h
        xs = x1 + (jnp.arange(k)[:, None] + (jnp.arange(S)[None, :] + 0.5) / S) * bin_w
        yy = jnp.clip(ys, 0, H - 1)
        xx = jnp.clip(xs, 0, W - 1)
        from jax.scipy.ndimage import map_coordinates
        img = data[b]  # (C,H,W)

        def pool_channel(d):
            # channel for output d, cell (i,j): group (gh,gw) = bucketed
            # cell position; ch = (d*gs + gh)*gs + gw (psroi_pooling.cc)
            def cell(i, j):
                gh = i * gs // k
                gw = j * gs // k
                ch = (d * gs + gh) * gs + gw
                grid_y = jnp.repeat(yy[i], S)
                grid_x = jnp.tile(xx[j], S)
                vals = map_coordinates(img[ch], [grid_y, grid_x], order=1,
                                       mode="nearest")
                return vals.mean()
            return jnp.stack([jnp.stack([cell(i, j) for j in range(k)])
                              for i in range(k)])

        return jnp.stack([pool_channel(d) for d in range(D)])  # (D,k,k)

    return jax.vmap(per_roi)(rois)
