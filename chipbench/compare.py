"""The comparison that decides ``correct``: served answers against the
reference's raw outputs, as numbers held to limits.

Classification (rows ``[class, probability]``, best first):

* ``logprob_err``: the widest gap between the log-probability a served row
  states and the reference's log-probability of the class it names;
* ``rank_gap``: the widest gap by which a served row's class lies below the
  reference's class of the same rank, in reference log-probability.

Detection (rows ``[x1, y1, x2, y2, score, class]``, zero rows unused):

* every served row is matched to the reference candidate of its class
  nearest to it in box corners plus score (scaled by the input size, so
  that boxes clipped to the same edges are told apart by their scores);
  ``box_err_px`` and ``score_err`` are the widest gaps of box corner and
  score to that candidate;
* ``nms_margin``: the served set of candidates against the reference's own
  survivors.  Each candidate in one set and not the other must owe that to
  a near-tie: its score within a hair of the score threshold or of the
  top-``max_det`` cut, its overlap with a same-class candidate within a hair
  of the IoU threshold, or its score within a hair of a same-class
  candidate it overlaps (which then suppresses which).  Its margin is the
  smallest such distance, or that of a differing candidate that suppresses
  it; ``nms_margin`` is the largest margin in the difference, 0 when the
  sets agree.  A sound run differs only on near-ties, so its margin is of
  the order of its score error; a wrong answer differs by a wide one.
"""

from __future__ import annotations

import numpy as np


def log_softmax(logits: np.ndarray) -> np.ndarray:
    z = logits.astype(np.float64)
    z = z - z.max(axis=-1, keepdims=True)
    return z - np.log(np.exp(z).sum(axis=-1, keepdims=True))


def compare_classify(served: np.ndarray, ref_logits: np.ndarray) -> dict:
    """served: (n, k, 2) rows; ref_logits: (n, classes) of the same
    inputs."""
    served = np.asarray(served, np.float64)
    logp = log_softmax(np.asarray(ref_logits))
    k = served.shape[1]
    cls = served[..., 0].astype(np.int64)
    ref_of_cls = np.take_along_axis(logp, cls, axis=1)
    ref_sorted = -np.sort(-logp, axis=1)[:, :k]
    stated = np.log(np.maximum(served[..., 1], 1e-300))
    return {"logprob_err": float(np.max(np.abs(stated - ref_of_cls))),
            "rank_gap": float(np.max(np.maximum(ref_sorted - ref_of_cls,
                                                0.0)))}


# ---- detection ---------------------------------------------------------

def _sigmoid(v):
    return 1.0 / (1.0 + np.exp(-v))


def yolo_candidates(feat: np.ndarray, head: dict, input_hw
                    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """One (Hg, Wg, A*(5+C)) map -> boxes (M, 4) x1y1x2y2 in network
    pixels, scores (M,) = objectness x best class probability, classes."""
    anchors = np.asarray(head["anchors"], np.float64)
    a, nc = len(anchors), head["n_classes"]
    hg, wg, ch = feat.shape
    if ch != a * (5 + nc):
        raise ValueError(f"map has {ch} channels, head wants {a * (5 + nc)}")
    f = feat.astype(np.float64).reshape(hg, wg, a, 5 + nc)
    cx = np.arange(wg)[None, :, None]
    cy = np.arange(hg)[:, None, None]
    bx = (_sigmoid(f[..., 0]) + cx) / wg
    by = (_sigmoid(f[..., 1]) + cy) / hg
    bw = anchors[:, 0] * np.exp(f[..., 2]) / wg
    bh = anchors[:, 1] * np.exp(f[..., 3]) / hg
    logits = f[..., 5:] - f[..., 5:].max(axis=-1, keepdims=True)
    probs = np.exp(logits) / np.exp(logits).sum(axis=-1, keepdims=True)
    score = _sigmoid(f[..., 4]) * probs.max(axis=-1)
    ih, iw = input_hw
    boxes = np.stack([np.clip((bx - bw / 2) * iw, 0, iw),
                      np.clip((by - bh / 2) * ih, 0, ih),
                      np.clip((bx + bw / 2) * iw, 0, iw),
                      np.clip((by + bh / 2) * ih, 0, ih)], axis=-1)
    return (boxes.reshape(-1, 4), score.reshape(-1),
            probs.argmax(axis=-1).reshape(-1))


def iou(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    lt = np.maximum(a[:, None, :2], b[None, :, :2])
    rb = np.minimum(a[:, None, 2:], b[None, :, 2:])
    inter = np.prod(np.clip(rb - lt, 0, None), axis=-1)
    area = lambda x: np.prod(np.clip(x[:, 2:] - x[:, :2], 0, None), axis=-1)
    union = area(a)[:, None] + area(b)[None, :] - inter
    return np.where(union > 0, inter / np.where(union > 0, union, 1), 0.0)


class FrameReference:
    """The reference's decode of one frame: candidates, the greedy
    class-aware NMS survivors over the top ``max_det`` candidates above
    the score threshold, and what the near-tie margins need."""

    def __init__(self, feat: np.ndarray, head: dict, input_hw):
        self.head = head
        self.scale = float(max(input_hw))
        self.boxes, self.scores, self.classes = yolo_candidates(
            feat, head, input_hw)
        k, thr = head["max_det"], head["score_thresh"]
        order = np.argsort(-self.scores, kind="stable")
        above = order[self.scores[order] >= thr]
        self.entrants = above[:k]
        # the candidates whose entry a near-tie can change: the entrants
        # and the first one left out
        self.near = above[:k + 1]
        s_sorted = self.scores[above]
        self.cut_gap = (float(s_sorted[k - 1] - s_sorted[k])
                        if len(above) > k else np.inf)
        self.cut_pair = set(above[k - 1:k + 1].tolist()) \
            if len(above) > k else set()
        keep: list[int] = []
        for i in self.entrants:
            same = [j for j in keep if self.classes[j] == self.classes[i]]
            if not same or np.all(iou(self.boxes[[i]], self.boxes[same])
                                  <= head["iou_thresh"]):
                keep.append(int(i))
        self.survivors = set(keep)

    def own_margin(self, d: int, pool: np.ndarray) -> float:
        """Distance of candidate ``d``'s NMS decision to its nearest
        boundary, against the same-class candidates in ``pool``."""
        h = self.head
        m = abs(self.scores[d] - h["score_thresh"])
        if d in self.cut_pair:
            m = min(m, self.cut_gap)
        others = [j for j in pool if j != d
                  and self.classes[j] == self.classes[d]]
        if others:
            ov = iou(self.boxes[[d]], self.boxes[others])[0]
            touching = ov > 0
            if touching.any():
                m = min(m, float(np.min(np.abs(ov[touching]
                                               - h["iou_thresh"]))))
            over = ov > h["iou_thresh"]
            if over.any():
                m = min(m, float(np.min(np.abs(
                    self.scores[np.asarray(others)[over]] - self.scores[d]))))
        return float(m)

    def diff_margin(self, served_ids: set[int]) -> float:
        """``nms_margin`` of one served answer (see module docstring)."""
        diff = served_ids ^ self.survivors
        if not diff:
            return 0.0
        pool = np.asarray(sorted(set(self.near.tolist()) | served_ids))
        margin = {d: self.own_margin(d, pool) for d in diff}
        # a differing candidate that clearly suppresses (or frees) another
        # passes its margin on
        changed = True
        while changed:
            changed = False
            for d in diff:
                for j in diff:
                    if j == d or self.classes[j] != self.classes[d]:
                        continue
                    if iou(self.boxes[[d]], self.boxes[[j]])[0, 0] \
                            > self.head["iou_thresh"] \
                            and margin[j] < margin[d]:
                        margin[d], changed = margin[j], True
        return max(margin.values())


def answer_rows(config: dict, raw: np.ndarray) -> np.ndarray:
    """A raw output decoded into the rows the program serves: the top-k
    ``[class, probability]`` rows, or the NMS survivors as
    ``[x1, y1, x2, y2, score, class]`` rows, best first, zero-padded."""
    head = config["head"]
    if config["task"] == "classify":
        p = np.exp(log_softmax(raw[None]))[0]
        top = np.argsort(-p, kind="stable")[:head["top_k"]]
        return np.stack([top.astype(np.float64), p[top]], axis=-1)
    ref = FrameReference(raw, head, config["input_hw"])
    keep = sorted(ref.survivors, key=lambda j: -ref.scores[j])
    rows = np.zeros((head["max_det"], 6))
    for r, j in enumerate(keep):
        rows[r] = [*ref.boxes[j], ref.scores[j], ref.classes[j]]
    return rows


def compare_detect(served: np.ndarray, frames: list[FrameReference],
                   frame_of: np.ndarray) -> dict:
    """served: (n, max_det, 6) rows; ``frame_of[i]`` indexes ``frames``,
    the reference of request i's input."""
    box_err = score_err = margin = 0.0
    for rows, fi in zip(np.asarray(served, np.float64), frame_of):
        ref = frames[fi]
        ids: set[int] = set()
        for row in rows[rows[:, 4] > 0]:
            same = np.flatnonzero(ref.classes == int(row[5]))
            if not same.size:
                box_err = score_err = np.inf
                continue
            dist = np.max(np.abs(ref.boxes[same] - row[:4]), axis=1)
            ds = np.abs(ref.scores[same] - row[4])
            # clipped boxes coincide: the score tells such candidates apart
            k = int(np.argmin(dist + ds * ref.scale))
            ids.add(int(same[k]))
            box_err = max(box_err, float(dist[k]))
            score_err = max(score_err, float(ds[k]))
        margin = max(margin, ref.diff_margin(ids))
    return {"box_err_px": box_err, "score_err": score_err,
            "nms_margin": margin}


def worst(numbers: dict, limits: dict) -> float:
    """The largest share of its limit that any number reaches."""
    return max(numbers.get(k, np.inf) / lim for k, lim in limits.items())


def resolve_ties(per_input: list[dict], hits: np.ndarray, rejudge,
                 limits: dict, rounds: int = 8
                 ) -> tuple[frozenset, list[dict], np.ndarray]:
    """The float32 ties (``reference.find_ties``) decided so that the
    served answers read closest to the reference.

    ``per_input[i]`` holds input i's numbers with no tie flipped,
    ``hits[i, t]`` whether input i hits tie t, and ``rejudge(flips, idx)``
    the numbers and hit rows of inputs ``idx`` with the ties in ``flips``
    flipped.  From none flipped, each round toggles the one tie that most
    lowers the worst share of a limit over all inputs (then the sum of
    each input's worst share), while one does.  Toggling a tie changes only
    the inputs that hit it, so only those are judged again.  Returns the
    flipped ties and the numbers and hits under them."""
    def score(per):
        shares = [worst(n, limits) for n in per]
        return max(shares), sum(shares)

    flips: frozenset = frozenset()
    hits = hits.copy()
    best = score(per_input)
    for _ in range(rounds):
        move = None
        for t in np.flatnonzero(hits.any(axis=0)):
            idx = np.flatnonzero(hits[:, t])
            nums, rows = rejudge(flips ^ {int(t)}, idx)
            trial = list(per_input)
            for k, n in zip(idx, nums):
                trial[k] = n
            s = score(trial)
            if s < best:
                best, move = s, (int(t), trial, idx, rows)
        if move is None:
            break
        t, per_input, idx, rows = move
        flips ^= {t}
        hits[idx] = rows
    return flips, per_input, hits


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Every number within its limit, and every limit given a number."""
    checks = {k: {"value": numbers.get(k), "limit": lim}
              for k, lim in limits.items()}
    ok = all(c["value"] is not None and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks
