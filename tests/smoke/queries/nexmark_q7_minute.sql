CREATE TABLE bids (
  datetime TIMESTAMP,
  auction BIGINT,
  price BIGINT,
  bidder TEXT
) WITH (
  connector = 'single_file',
  path = '$input_dir/bids.json',
  format = 'json',
  type = 'source',
  event_time_field = 'datetime'
);
CREATE TABLE highest_bids (
  auction BIGINT,
  price BIGINT
) WITH (
  connector = 'single_file',
  path = '$output_path',
  format = 'json',
  type = 'sink'
);
INSERT INTO highest_bids
SELECT PerAuction.auction, PerAuction.mx
FROM (
  SELECT auction, max(price) AS mx,
    tumble(interval '1 minute') AS window
  FROM bids GROUP BY auction, window
) AS PerAuction
JOIN (
  SELECT max(price) AS mx,
    tumble(interval '1 minute') AS window
  FROM bids GROUP BY window
) AS GlobalMax
ON PerAuction.window = GlobalMax.window AND PerAuction.mx = GlobalMax.mx;
