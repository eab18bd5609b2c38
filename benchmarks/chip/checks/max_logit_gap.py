"""The widest gap, over every sampled served position, by which the
served token's logit lies below the reference's best."""


def read(gaps):
    return float(gaps.max())
