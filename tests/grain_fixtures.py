"""Grain dataset factories used by test_grain_reader."""


def dict_dataset(n: int = 8):
    import grain

    return grain.MapDataset.range(n).map(
        lambda i: {"image": [i] * 4, "label": i % 2}
    )
