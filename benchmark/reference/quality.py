"""Plain quality metrics. Used as floors only, never as two-sided bands."""
import numpy as np


def auc(label, score):
    """Area under the ROC curve by ranks, ties given their mean rank."""
    order = np.argsort(score, kind="mergesort")
    s = score[order]
    rank = np.empty(len(s), dtype=np.float64)
    start = np.flatnonzero(np.r_[True, s[1:] != s[:-1]])
    end = np.r_[start[1:], len(s)]
    mean_rank = (start + end + 1) / 2.0
    rank[order] = np.repeat(mean_rank, end - start)
    pos = label > 0
    n1, n0 = int(pos.sum()), int((~pos).sum())
    return (rank[pos].sum() - n1 * (n1 + 1) / 2.0) / (n1 * n0)


def ndcg_at(label, score, group, k):
    """Mean NDCG@k over queries, gain 2**label - 1, discount 1/log2(rank+1);
    a query with no relevant document counts 1 (the reference's rule)."""
    total, start = 0.0, 0
    disc = 1.0 / np.log2(np.arange(2, k + 2))
    for size in group:
        lab = label[start:start + size]
        sc = score[start:start + size]
        start += size
        gain = 2.0 ** lab - 1.0
        top = min(k, size)
        ideal = (np.sort(gain)[::-1][:top] * disc[:top]).sum()
        if ideal <= 0:
            total += 1.0
            continue
        got = (gain[np.argsort(-sc, kind="mergesort")[:top]] * disc[:top]).sum()
        total += got / ideal
    return total / len(group)
