"""Quickstart: the paper's Figure-1 workflow, end to end.

An analyst explores iPhone price/rating/feature relationships: ingest →
point-fix a data error (C1) → transpose (C2) → clean a column with map (C3) →
load a second table (C4) → one-hot encode (A1) → join (A2) → covariance (A3).

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import sys

sys.path.insert(0, "src")

from repro import compile_cache
from repro.core import DataFrame, EvalMode, Session, get_dummies, set_session


def main():
    compile_cache.enable()
    set_session(Session(mode=EvalMode.OPPORTUNISTIC))

    # In[1] — ingest the scraped comparison chart (products as columns)
    products = DataFrame({
        "iPhone 11 Pro": ["5.8-inch", "12MP", "120MP", "Yes"],
        "iPhone 11 Pro Max": ["6.5-inch", "12MP", "12MP", "Yes"],
        "iPhone XR": ["6.1-inch", "12MP", "7MP", "No"],
        "iPhone 8 Plus": ["5.5-inch", "12MP", "7MP", "No"],
    }, row_labels=["Display", "Camera", "Front Camera", "Wireless Charging"])
    print("Out[1]:", products.head(4).to_pydict())

    # C1 — ordered point update: the 120MP front camera is a data-entry error
    products.iloc[2, 0] = "12MP"
    print("Out[2]: front camera fixed →", products.iloc[2, 0])

    # C2 — matrix-like transpose: products become rows
    products = products.T
    print("Out[3]:", products.head(4).to_pydict())

    # C3 — column transformation via a user-defined map (+ schema induction)
    products["Wireless Charging"] = products["Wireless Charging"].map(
        lambda v: 1 if v == "Yes" else 0)
    print("Out[4]:", products.collect().induce().schema)

    # C4 — read the second dataset (prices & ratings)
    prices = DataFrame({
        "model": ["iPhone 11 Pro", "iPhone 11 Pro Max", "iPhone XR",
                  "iPhone 8 Plus"],
        "price": [999, 1099, 599, 449],
        "rating": [4.5, 4.6, 4.4, 4.3],
    })
    print("Out[5]:", prices.head(4).to_pydict())

    # A1 — one-hot encode categorical features
    one_hot = get_dummies(products.reset_index("model"), ["Display"])
    print("Out[6] cols:", one_hot.columns)

    # A2 — join with prices on the model name
    joined = one_hot.merge(prices, on="model")

    # A3 — covariance across the numeric features (a matrix dataframe)
    numeric = joined[[c for c in joined.columns
                      if c not in ("model", "Camera", "Front Camera")]]
    cov = numeric.cov()
    print("Out[7] covariance matrix:")
    names = cov.col_labels.to_list()
    for name, row in zip(names, cov.to_records()):
        print(f"  {name:22s}", " ".join(f"{v:8.2f}" for v in row))


if __name__ == "__main__":
    main()
